"""Least time by the chip's peaks for the rules the traced steps ran (gdn_cost.gdn_rule_cost of each call: rows, length, key and value heads, widths, chunk 64) over the device time of scope `gdn_rule`."""

from benchmark import gdn_trace


def read(records):
    return gdn_trace.rule_roofline(records)
