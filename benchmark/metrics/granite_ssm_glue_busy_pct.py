"""Device self time of a Mamba-2 block's elementwise stages around the scan (scopes `ssm_conv`: the depthwise causal convolution and its silu; `ssm_gate_norm`: the gate and the norm over all of d_inner) over device busy time."""

from benchmark import granite_trace


def read(records):
    return granite_trace.scope_busy_pct(records, "ssm_conv", "ssm_gate_norm")
