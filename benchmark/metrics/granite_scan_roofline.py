"""Least time by the chip's peaks for the scans the traced steps ran (ssm_cost.ssd_scan_cost of each call: rows, length, chunk, heads, head size, groups, states) over the device time of scope `ssm_scan`."""

from benchmark import granite_trace


def read(records):
    return granite_trace.scan_roofline(records)
