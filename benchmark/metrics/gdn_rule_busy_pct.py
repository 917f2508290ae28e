"""Device self time under the scope `gdn_rule` (the gated delta rule in chunks: the [Q, Q] blocks, the inverse, the scan over the chunks' states; forward, the forwards the checkpoints re-run, and backward) over busy time."""

from benchmark import gdn_trace


def read(records):
    return gdn_trace.scope_busy_pct(records, "gdn_rule")
