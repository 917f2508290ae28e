"""Device self time of the shared expert of the expert blocks (scope `shared_expert`) over device busy time."""

from benchmark import mla_trace


def read(records):
    return mla_trace.scope_busy_pct(records, "shared_expert")
