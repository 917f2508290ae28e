"""Device time of the Pallas flash kernels (fwd + bwd) over device busy time, from the trace."""

from benchmark import readers


def read(records):
    return readers.flash_attn_busy_pct(records)
