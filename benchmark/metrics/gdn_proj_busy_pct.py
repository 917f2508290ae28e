"""Device self time under the scopes `gdn_in_proj` and `gdn_out_proj` (a Gated DeltaNet mixer's three projections) over busy time."""

from benchmark import gdn_trace


def read(records):
    return gdn_trace.scope_busy_pct(records, "gdn_in_proj", "gdn_out_proj")
