"""Device self time under the scopes `moe_router` (matmul over all routed experts, softmax, top-k) and `moe_dispatch` (sort, gather, un-permute, combine) on a share of the expert layer, over device busy time."""

from benchmark import moe_trace


def read(records):
    return moe_trace.scope_busy_pct(records, "moe_router", "moe_dispatch")
