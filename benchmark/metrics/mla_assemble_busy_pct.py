"""Device self time under the scope `mla_assemble` (the narrow RoPE, the shared rotary key's broadcast, both concatenates) over busy time."""

from benchmark import mla_trace


def read(records):
    return mla_trace.scope_busy_pct(records, "mla_assemble")
