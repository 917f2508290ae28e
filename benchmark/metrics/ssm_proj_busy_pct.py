"""Device self time of a Mamba-2 mixer around its scan (scopes `ssm_in_proj`, `ssm_conv`, `ssm_gate_norm`, `ssm_out_proj`) over device busy time."""

from benchmark import ssm_trace


def read(records):
    return ssm_trace.scope_busy_pct(
        records, "ssm_in_proj", "ssm_conv", "ssm_gate_norm", "ssm_out_proj")
