"""Device self time of the ops under the scope `mlp` (forward, remat and backward) over device busy time."""

from benchmark import program_trace


def read(records):
    return program_trace.scope_busy_pct(records, "mlp", "moe")
