"""The stopwatch's set-up (imports + weights and backend + warm-up) less the
union of all compile-ledger spans: imports, weights, uploads and first
executions, which no span covers yet."""

from benchmark import setup_ledger


def read(records):
    return setup_ledger.unattributed_s(records)
