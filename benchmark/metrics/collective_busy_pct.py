"""Device self time of every collective op, whatever its scope (the expert exchange and the gradient all-reduce), over device busy time."""

from benchmark import moe_trace


def read(records):
    return moe_trace.collective_busy_pct(records)
