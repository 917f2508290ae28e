"""Least time by the chip's peaks for the traced steps' windowed attention calls at window 512 and the published 40 / 20 heads of 64 (window_trace.window_attention_cost) over the kernels' time."""

from benchmark import sambay_trace


def read(records):
    return sambay_trace.window_roofline(records)
