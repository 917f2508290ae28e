"""Least time by the chip's peaks for the traced steps' windowed attention calls (window_trace.window_attention_cost: per query block the keys of min(position, window) plus one tile) over the kernels' time."""

from benchmark import window_trace


def read(records):
    return window_trace.window_attn_roofline(records)
