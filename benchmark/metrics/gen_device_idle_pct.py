"""Idle share of the worst chip in the traced part of the window (rollout cells)."""

from benchmark import readers


def read(records):
    return readers.device_idle_pct(records)
