"""Device self time under the scopes `mla_q_proj`, `mla_kv_down`, `mla_kv_up` and `o_proj` (latent attention's four matmuls without a query latent and the latent norm) over busy time."""

from benchmark import kimi_trace


def read(records):
    return kimi_trace.mla_proj_busy_pct(records)
