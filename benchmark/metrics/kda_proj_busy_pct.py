"""Device self time under the scopes `kda_in_proj` and `kda_out_proj` (the three projections with the three narrow ones, and the out-projection) over busy time."""

from benchmark import kimi_trace


def read(records):
    return kimi_trace.scope_busy_pct(records, *kimi_trace.KDA_PROJ_SCOPES)
