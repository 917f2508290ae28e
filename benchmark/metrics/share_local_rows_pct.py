"""(token, expert) pairs that chose an expert held on this chip over all pairs routed, over the window's steps; from the steps' statistics."""

from benchmark import window_trace


def read(records):
    return window_trace.share_local_rows_pct(records)
