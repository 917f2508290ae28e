"""Self time of the grouped-head causal kernels (by op name) on the attention block at heads of 256 over busy time."""

from benchmark import window_trace


def read(records):
    return window_trace.window_attn_busy_pct(records)
