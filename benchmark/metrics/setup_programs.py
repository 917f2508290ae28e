"""Backend compile calls in set-up (the programs' own compile spans in the
compile ledger): how many executables the cell's grids, passes and branches
need."""

from benchmark import setup_ledger


def read(records):
    return setup_ledger.programs(records)
