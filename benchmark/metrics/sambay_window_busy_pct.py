"""Device self time of the windowed (splash) kernels' own ops, forward and backward, over device busy time."""

from benchmark import window_trace


def read(records):
    return window_trace.window_attn_busy_pct(records)
