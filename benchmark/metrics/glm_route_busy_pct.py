"""Device self time under the scopes `moe_router` and `moe_dispatch` (64-wide sigmoid and top-4 of score + bias, the sort of 4 assignments a token, the gathers and the combine) over busy time."""

from benchmark import moe_trace


def read(records):
    return moe_trace.scope_busy_pct(records, "moe_router", "moe_dispatch")
