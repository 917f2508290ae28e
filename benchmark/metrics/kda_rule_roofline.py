"""Least time by the chip's peaks for the rules the traced steps ran (kda_cost.kda_rule_cost of each call: rows, length, 32 heads of 128 / 128, a decay a key channel, chunk 64) over the device time of scope `kda_rule`."""

from benchmark import kimi_trace


def read(records):
    return kimi_trace.rule_roofline(records)
