"""Device self time under the scope `diff_attn_combine` (differential attention's lambda-combine, sub-norm and scale, behind every attention kernel) over device busy time."""

from benchmark import sambay_trace


def read(records):
    return sambay_trace.scope_busy_pct(records, "diff_attn_combine")
