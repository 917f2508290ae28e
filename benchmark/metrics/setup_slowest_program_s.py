"""The largest trace + lower + compile of ONE compilation of one program in
set-up (the compile ledger's max_secs)."""

from benchmark import setup_ledger


def read(records):
    return setup_ledger.slowest_program_s(records)
