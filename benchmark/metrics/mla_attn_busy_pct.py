"""Self time of the grouped-head causal kernels (by op name) at 20 / 20 heads of 256, behind the latent projection path, over busy time."""

from benchmark import window_trace


def read(records):
    return window_trace.window_attn_busy_pct(records)
