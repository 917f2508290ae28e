"""Documents over the packed rows that hold any, of the train step's grids (the program's gauge `train/docs_per_row`), averaged over the window's train batches: how often a row's scans, convolutions and attention masks start over."""

from benchmark import granite_trace


def read(records):
    return granite_trace.docs_per_row(records)
