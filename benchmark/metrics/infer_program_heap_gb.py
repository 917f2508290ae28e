"""The compiler's temporaries of the inference program (`infer_forward`)
that needs most, one chip's: where a head that runs over a whole row's
logits shows."""

from benchmark import program_memory


def read(records):
    return program_memory.heap_gb(records, program_memory.INFER_PROGRAM)
