"""Device self time under the scopes `moe_router` and `moe_dispatch` (512-wide softmax and top-10, the sort of 163,840 assignments a micro-batch, the gathers and the combine) over busy time."""

from benchmark import moe_trace


def read(records):
    return moe_trace.scope_busy_pct(records, "moe_router", "moe_dispatch")
