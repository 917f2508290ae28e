"""Least time by the chip's HBM peak for the assemblies the traced steps needed (mla_cost.assemble_cost: 58 KB a token a block each way) over the device time of scope `mla_assemble`."""

from benchmark import mla_trace


def read(records):
    return mla_trace.assemble_roofline(records)
