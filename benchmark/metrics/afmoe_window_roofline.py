"""Least time by the chip's peaks for the traced steps' windowed attention calls at window 2048 and the published heads (window_trace.window_attention_cost) over the kernels' time."""

from benchmark import window_trace


def read(records):
    return window_trace.window_attn_roofline(records)
