"""Peak device memory of the fullest chip over all device-owning workers (async cell)."""

from benchmark import readers


def read(records):
    return readers.hbm_peak_gb(records)
