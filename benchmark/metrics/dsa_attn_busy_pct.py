"""Device self time under scope dsa_attention (the kernels dsa_attend_fwd / dq / dkv, each making its tiles' mask again) over the traced window's busy time."""

from benchmark import dsa_trace


def read(records):
    return dsa_trace.attn_busy_pct(records)
