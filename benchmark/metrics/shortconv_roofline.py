"""Least time by the chip's HBM peak for the doubly gated convolutions the traced steps needed (shortconv_cost.glue_cost: 16 KB a token a block forward, 28 KB backward) over the device time of scope `shortconv`."""

from benchmark import shortconv_trace


def read(records):
    return shortconv_trace.conv_roofline(records)
