"""Least time by the chip's peaks for the selective scans the traced steps ran (sambay_cost.selective_scan_cost of each call: rows, length, d_inner, states) over the device time of scope `s6_scan`."""

from benchmark import sambay_trace


def read(records):
    return sambay_trace.scan_roofline(records)
