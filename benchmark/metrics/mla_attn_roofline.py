"""Least time by the chip's peaks for the traced steps' causal attention calls at 20 / 20 heads of 256, a DOCUMENT at a time (mla_cost.attention_cost), over the kernels' time."""

from benchmark import mla_trace


def read(records):
    return mla_trace.attn_roofline(records)
