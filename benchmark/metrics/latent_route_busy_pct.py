"""Device self time of the sigmoid router and of the sort, gather, un-permute and combine around the latent experts (scopes `moe_router`, `moe_dispatch`) over device busy time."""

from benchmark import ssm_trace


def read(records):
    return ssm_trace.scope_busy_pct(records, "moe_router", "moe_dispatch")
