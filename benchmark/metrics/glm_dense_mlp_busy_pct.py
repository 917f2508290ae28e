"""Device self time of the leading dense block's FFN at width 10,240 (scope `mlp`) over device busy time."""

from benchmark import mla_trace


def read(records):
    return mla_trace.scope_busy_pct(records, "mlp")
