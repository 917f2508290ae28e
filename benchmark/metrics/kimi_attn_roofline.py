"""Least time by the chip's peaks for the causal attention the traced steps ran, a document at a time at the published 192 / 128 (kda_cost.attention_cost), over the grouped-head kernels' own time."""

from benchmark import kimi_trace


def read(records):
    return kimi_trace.attn_roofline(records)
