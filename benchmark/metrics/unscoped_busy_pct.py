"""Device self time of ops that carry no scope name of the program's list, over busy time: what the names do not cover."""

from benchmark import program_trace


def read(records):
    return program_trace.scope_busy_pct(records, program_trace.UNSCOPED)
