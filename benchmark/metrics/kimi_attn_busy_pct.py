"""Self time of the grouped-head causal kernels (`splash_*` by name, and the `closed_call.N` a call batched over a grid's rows becomes, under the scope `causal_attention`) at 32 / 32 heads of 192 over a value of 128, behind the latent projection path, over busy time."""

from benchmark import kimi_trace


def read(records):
    return kimi_trace.attn_busy_pct(records)
