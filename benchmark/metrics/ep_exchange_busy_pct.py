"""Device self time under the scope `moe_exchange` (the expert layer's collectives over the `ep` axis) over device busy time."""

from benchmark import moe_trace


def read(records):
    return moe_trace.scope_busy_pct(records, "moe_exchange")
