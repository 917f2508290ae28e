"""Executables that set-up compiled and wrote to the persistent cache (jax's
own event): 0 in a warm run, every cached program in a cold one."""

from benchmark import setup_ledger


def read(records):
    return setup_ledger.cache_misses(records)
