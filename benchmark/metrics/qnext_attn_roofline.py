"""Least time by the chip's peaks for the traced steps' causal attention calls at 16 / 2 heads of 256 (gdn_cost.attention_cost) over the kernels' time."""

from benchmark import gdn_trace


def read(records):
    return gdn_trace.attn_roofline(records)
