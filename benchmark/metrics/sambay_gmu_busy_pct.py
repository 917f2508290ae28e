"""Device self time under the scope `gmu` (a gated memory unit: two matmuls around a gate on another layer's scan output) over device busy time."""

from benchmark import sambay_trace


def read(records):
    return sambay_trace.scope_busy_pct(records, "gmu")
