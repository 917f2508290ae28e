"""Device self time under the scopes `kda_conv`, `kda_gates` and `kda_gate_norm` (the convolutions with their SiLU, beta, the decay through its bottleneck, the l2 norms, the gated output norm with its gate's expansion) over busy time."""

from benchmark import kimi_trace


def read(records):
    return kimi_trace.scope_busy_pct(records, *kimi_trace.KDA_GLUE_SCOPES)
