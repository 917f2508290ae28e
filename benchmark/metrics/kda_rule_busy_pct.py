"""Device self time under the scope `kda_rule` (the kernels `kda_rule_fwd` / `kda_rule_bwd` and their layout glue) over busy time."""

from benchmark import kimi_trace


def read(records):
    return kimi_trace.scope_busy_pct(records, "kda_rule")
