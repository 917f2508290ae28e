"""Device self time under the scopes `s6_in_proj`, `s6_conv`, `s6_xdt_proj` and `s6_out_proj` (a Mamba-1 mixer but its scan) over device busy time."""

from benchmark import sambay_trace


def read(records):
    return sambay_trace.scope_busy_pct(
        records, "s6_in_proj", "s6_conv", "s6_xdt_proj", "s6_out_proj")
