"""Median ``timeperf/e2e`` of the master's step lines in the window."""

import statistics


def read(records):
    steps = records.get("master_steps") or []
    vals = [s["timeperf/e2e"] for s in steps if "timeperf/e2e" in s]
    return statistics.median(vals) if vals else None
