"""Device self time of the sandwich norms on each branch's output (scopes `post_attn_norm`, `post_mlp_norm`) over device busy time."""

from benchmark import afmoe_trace


def read(records):
    return afmoe_trace.scope_busy_pct(
        records, "post_attn_norm", "post_mlp_norm")
