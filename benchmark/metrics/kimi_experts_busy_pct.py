"""Device self time under the scope `moe_experts` (the grouped GEMMs by op name and the elementwise ops between them) over busy time."""

from benchmark import moe_trace


def read(records):
    return moe_trace.scope_busy_pct(records, "moe_experts")
