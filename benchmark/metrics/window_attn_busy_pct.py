"""Device self time of the windowed attention kernels (jax's splash kernels, by op name: forward, dKV, dQ) over device busy time."""

from benchmark import window_trace


def read(records):
    return window_trace.window_attn_busy_pct(records)
