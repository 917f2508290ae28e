"""Least time by the chip's peaks for the traced steps' causal attention calls at 32 / 8 heads of 64, a DOCUMENT at a time (shortconv_cost.attention_cost), over the kernels' time."""

from benchmark import shortconv_trace


def read(records):
    return shortconv_trace.attn_roofline(records)
