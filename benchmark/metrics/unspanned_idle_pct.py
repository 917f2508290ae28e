"""Device idle time under no `areal/` span, over all idle time of the traced window: what the spans do not cover."""

from benchmark import program_trace


def read(records):
    return program_trace.unspanned_idle_pct(records)
