"""Device self time of gated attention's own work — the projection x W_g and the multiply by its sigmoid (scope `attn_gate`) — over device busy time."""

from benchmark import afmoe_trace


def read(records):
    return afmoe_trace.scope_busy_pct(records, "attn_gate")
