"""Device self time of the leading dense block's FFN at width 11,776 (scope `mlp`) over device busy time."""

from benchmark import shortconv_trace


def read(records):
    return shortconv_trace.scope_busy_pct(records, "mlp")
