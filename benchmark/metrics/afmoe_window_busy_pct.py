"""Device self time of the windowed (splash) attention kernels at window 2048 — forward, dK/dV and dQ, by op name — over device busy time."""

from benchmark import window_trace


def read(records):
    return window_trace.window_attn_busy_pct(records)
