"""Least time by the chip's peaks for the traced steps' grouped GEMMs over the held experts (moe_cost.grouped_ffn_cost of the rows that landed on this chip) over the device time of the scope `moe_experts`."""

from benchmark import window_trace


def read(records):
    return window_trace.share_experts_roofline(records)
