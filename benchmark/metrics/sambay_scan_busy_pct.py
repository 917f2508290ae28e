"""Device self time under the scope `s6_scan` (the selective-scan kernels forward and backward, the float32 casts and column layouts they are handed, the skip `D x`) over device busy time."""

from benchmark import sambay_trace


def read(records):
    return sambay_trace.scope_busy_pct(records, "s6_scan")
