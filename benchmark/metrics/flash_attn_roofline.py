"""Least time by the chip's peaks for the traced flash calls' shapes over the kernels' time."""

from benchmark import readers


def read(records):
    return readers.flash_attn_roofline(records)
