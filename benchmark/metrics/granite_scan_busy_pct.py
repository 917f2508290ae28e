"""Device self time of the chunked state-space scan inside a whole block (scope `ssm_scan` at chunk 256, 32 heads, one group: decays, the masked [Q, Q] products, chunk states, softplus and the D skip; forward, re-run and backward) over device busy time."""

from benchmark import granite_trace


def read(records):
    return granite_trace.scope_busy_pct(records, "ssm_scan")
