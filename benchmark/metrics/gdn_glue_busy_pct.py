"""Device self time under the scopes `gdn_conv`, `gdn_gates` and `gdn_gate_norm` (the convolution with its SiLU, beta / g / the l2 norms of q and k, the gated output norm) over busy time."""

from benchmark import gdn_trace


def read(records):
    return gdn_trace.scope_busy_pct(records, "gdn_conv", "gdn_gates", "gdn_gate_norm")
