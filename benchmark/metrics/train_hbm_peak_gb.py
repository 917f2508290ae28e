"""Peak device memory of the fullest chip, as memory_stats() reports it (train cell)."""

from benchmark import readers


def read(records):
    return readers.hbm_peak_gb(records)
