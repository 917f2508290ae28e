"""Least time by the chip's peaks for attention over the SELECTED pairs of the traced steps' documents at 32 / 4 heads of 128 (dsa_cost.attention_cost), over the device time of scope dsa_attention."""

from benchmark import dsa_trace


def read(records):
    return dsa_trace.attn_roofline(records)
