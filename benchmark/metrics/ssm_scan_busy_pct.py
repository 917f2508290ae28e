"""Device self time of the chunked state-space scan (scope `ssm_scan`: decays, the masked [Q, Q] products, chunk states, softplus and the D skip; forward, re-run and backward) over device busy time."""

from benchmark import ssm_trace


def read(records):
    return ssm_trace.scope_busy_pct(records, "ssm_scan")
