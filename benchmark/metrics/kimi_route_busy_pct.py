"""Device self time under the scopes `moe_router` and `moe_dispatch` (256-wide sigmoid and top-8 of score + bias, the sort of 8 assignments a token, the gathers and the combine) over busy time."""

from benchmark import moe_trace


def read(records):
    return moe_trace.scope_busy_pct(records, "moe_router", "moe_dispatch")
