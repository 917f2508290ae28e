"""Least time by the chip's peaks for the traced steps' grouped GEMMs at K 2048 / N 512 over the rows that landed on the held experts (window_trace.share_experts_roofline) over the device time of the scope `moe_experts`."""

from benchmark import window_trace


def read(records):
    return window_trace.share_experts_roofline(records)
