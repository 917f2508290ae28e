"""Set-up seconds inside a trace or lower span of the compile ledger and not
inside a backend compile: the part of set-up no compilation cache saves."""

from benchmark import setup_ledger


def read(records):
    return setup_ledger.trace_lower_s(records)
