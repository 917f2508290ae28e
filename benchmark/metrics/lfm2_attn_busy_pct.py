"""Self time of the grouped-head causal kernels (by op name) on the attention block at 32 / 8 heads of 64 over busy time."""

from benchmark import window_trace


def read(records):
    return window_trace.window_attn_busy_pct(records)
