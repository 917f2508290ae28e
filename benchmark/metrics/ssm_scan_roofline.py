"""Least time by the chip's peaks for the scans the traced steps ran (ssm_cost.ssd_scan_cost of each call: rows, length, chunk, heads, groups) over the device time of scope `ssm_scan`."""

from benchmark import ssm_trace


def read(records):
    return ssm_trace.ssm_scan_roofline(records)
