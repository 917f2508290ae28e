"""Document starts at which the taps were cut (a start behind another document of its row) over the packed rows that hold any document, of the train step's grids (the program's gauge `train/shortconv_resets_per_row`), averaged over the window's train batches."""

from benchmark import shortconv_trace


def read(records):
    return shortconv_trace.resets_per_row(records)
