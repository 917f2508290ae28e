"""Device self time under the scope `attention` less the flash kernels' own ops (K/V repeat, head padding, transposes) over busy time."""

from benchmark import program_trace


def read(records):
    return program_trace.scope_busy_pct(records, "attention")
