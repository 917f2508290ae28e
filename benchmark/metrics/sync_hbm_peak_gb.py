"""Peak device memory of the fullest chip in the cell with weight bumps:
the shadow swap holds the old and the new weights together."""

from benchmark import readers


def read(records):
    return readers.hbm_peak_gb(records)
