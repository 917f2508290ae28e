"""Device self time under the scope `shared_expert` (its three matmuls and, inside `shared_expert_gate`, the sigmoid gate a token) over busy time."""

from benchmark import ssm_trace


def read(records):
    return ssm_trace.scope_busy_pct(records, "shared_expert")
