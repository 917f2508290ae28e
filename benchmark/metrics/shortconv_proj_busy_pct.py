"""Device self time under the scopes `shortconv_in_proj` and `shortconv_out_proj` (a short-convolution mixer's two projections) over busy time."""

from benchmark import shortconv_trace


def read(records):
    return shortconv_trace.scope_busy_pct(
        records, "shortconv_in_proj", "shortconv_out_proj")
