"""Device self time of the grouped GEMMs over the held latent experts (scope `moe_experts`, the `ragged-dot*` ops by name) over device busy time."""

from benchmark import ssm_trace


def read(records):
    return ssm_trace.scope_busy_pct(records, "moe_experts")
