"""The largest compiler's temporaries of ANY executable compiled by the
end of warm-up, one chip's: `train_hbm_peak_gb` plus this bounds what the
cell needs of the chip from above."""

from benchmark import program_memory


def read(records):
    return program_memory.heap_gb(records)
