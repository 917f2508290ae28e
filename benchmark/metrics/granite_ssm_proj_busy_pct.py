"""Device self time of a Mamba-2 block's two projections (scopes `ssm_in_proj`, `ssm_out_proj`) over device busy time."""

from benchmark import granite_trace


def read(records):
    return granite_trace.scope_busy_pct(records, "ssm_in_proj",
                                        "ssm_out_proj")
