"""Device self time of the ops under the scope `moe_experts` (the grouped expert GEMMs, every pass) over device busy time."""

from benchmark import moe_trace


def read(records):
    return moe_trace.scope_busy_pct(records, "moe_experts")
