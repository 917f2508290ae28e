"""Device self time under the scopes `qkv_proj`, `rope` and `o_proj` over device busy time."""

from benchmark import program_trace


def read(records):
    return program_trace.scope_busy_pct(records, "qkv_proj", "rope", "o_proj")
