"""Document starts that fall inside a chunk of the rule (off the 64-token grid) over the packed rows that hold any document, of the train step's grids (the program's gauge `train/kda_resets_in_chunk_per_row`), averaged over the window's train batches."""

from benchmark import kimi_trace


def read(records):
    return kimi_trace.resets_in_chunk_per_row(records)
