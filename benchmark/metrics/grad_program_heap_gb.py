"""The compiler's temporaries of the grad program (`train_grad_sliced`)
that needs most, one chip's: the heap `train_hbm_peak_gb` does not see, of
the program that decides what the backward re-runs."""

from benchmark import program_memory


def read(records):
    return program_memory.heap_gb(records, program_memory.GRAD_PROGRAM)
