"""Device self time of the leading dense block's FFN (scope `mlp`, its post-norm apart) over device busy time."""

from benchmark import afmoe_trace


def read(records):
    return afmoe_trace.scope_busy_pct(records, "mlp")
