"""Device time inside the program `train_apply` (clip, Adam, parameter update) over device busy time."""

from benchmark import program_trace


def read(records):
    return program_trace.program_busy_pct(records, "train_apply")
