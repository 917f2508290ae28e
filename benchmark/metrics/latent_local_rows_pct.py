"""(token, expert) pairs that chose an expert held on this chip over all pairs routed (8 of 512 held: 1.56 % under an even router), over the window's steps; from the steps' statistics."""

from benchmark import ssm_trace


def read(records):
    return ssm_trace.latent_local_rows_pct(records)
