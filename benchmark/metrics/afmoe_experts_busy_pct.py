"""Device self time of the grouped expert GEMMs over the 8 held experts of the expert blocks (scope `moe_experts`, the GEMM ops by name) over device busy time."""

from benchmark import moe_trace


def read(records):
    return moe_trace.scope_busy_pct(records, "moe_experts")
