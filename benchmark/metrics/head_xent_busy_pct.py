"""Device self time under the scopes `head` (the tied 152k-wide matmul) and `xent` (its logsumexp and gather) over busy time."""

from benchmark import program_trace


def read(records):
    return program_trace.scope_busy_pct(records, "head", "xent")
