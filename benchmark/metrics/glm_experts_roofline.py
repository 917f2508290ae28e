"""Least time by the chip's peaks for the traced steps' grouped GEMMs at K 2048 / N 1536 over the rows that landed on the 8 held experts, on the expert blocks (mla_trace.experts_roofline), over the device time of the scope `moe_experts`."""

from benchmark import mla_trace


def read(records):
    return mla_trace.experts_roofline(records)
