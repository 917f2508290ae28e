"""Least time by the chip's peaks for the traced steps' grouped expert GEMMs (moe_cost) over the device time of the scope `moe_experts`."""

from benchmark import moe_trace


def read(records):
    return moe_trace.experts_roofline(records)
