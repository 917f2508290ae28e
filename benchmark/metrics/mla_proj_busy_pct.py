"""Device self time under the scopes `mla_q_proj`, `mla_kv_down`, `mla_kv_up` and `o_proj` (latent attention's five matmuls and the two latent norms) over busy time."""

from benchmark import mla_trace


def read(records):
    return mla_trace.scope_busy_pct(records, *mla_trace.PROJ_SCOPES)
