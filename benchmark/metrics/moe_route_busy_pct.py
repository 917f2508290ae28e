"""Device self time under the scopes `moe_router` (matmul, softmax, top-k) and `moe_dispatch` (sort, gather, un-permute, combine) over device busy time."""

from benchmark import moe_trace


def read(records):
    return moe_trace.scope_busy_pct(records, "moe_router", "moe_dispatch")
