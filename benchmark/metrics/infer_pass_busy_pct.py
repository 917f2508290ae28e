"""Device time inside the program `infer_forward` (the proximal-logprob forward) over device busy time."""

from benchmark import program_trace


def read(records):
    return program_trace.program_busy_pct(records, "infer_forward")
