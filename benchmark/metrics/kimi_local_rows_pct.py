"""(token, expert) pairs that chose one of the 8 experts held on this chip over all pairs routed (8 / 256 under an even router), over the window's steps; from the steps' statistics."""

from benchmark import window_trace


def read(records):
    return window_trace.share_local_rows_pct(records)
