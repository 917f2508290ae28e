"""Device self time under the scopes `moe_router` (matmul over the 128 routed experts, sigmoid, top-k) and `moe_dispatch` (sort, gather, combine) on a share of the expert blocks, over device busy time."""

from benchmark import moe_trace


def read(records):
    return moe_trace.scope_busy_pct(records, "moe_router", "moe_dispatch")
