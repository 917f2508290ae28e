"""Device self time of the shared expert of the expert blocks (scope `shared_expert`) over device busy time."""

from benchmark import ssm_trace


def read(records):
    return ssm_trace.scope_busy_pct(records, "shared_expert")
