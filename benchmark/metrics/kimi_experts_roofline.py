"""Least time by the chip's peaks for the traced steps' grouped GEMMs at K 2304 / N 1024 over the rows that landed on the 8 held experts, on the expert blocks (kimi_trace.experts_roofline), over the device time of the scope `moe_experts`."""

from benchmark import kimi_trace


def read(records):
    return kimi_trace.experts_roofline(records)
