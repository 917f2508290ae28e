"""Device self time under the scope `cross_attention` (a cross layer's q and o projections and the flash kernel over another layer's K/V) over device busy time."""

from benchmark import sambay_trace


def read(records):
    return sambay_trace.scope_busy_pct(records, "cross_attention")
