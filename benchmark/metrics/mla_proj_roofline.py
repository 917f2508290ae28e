"""Least time by the chip's bf16 peak for the five projections the traced steps needed (mla_cost.projection_cost) over the device time of their scopes."""

from benchmark import mla_trace


def read(records):
    return mla_trace.proj_roofline(records)
