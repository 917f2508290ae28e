"""Median wait between the publish of a version and the servers' first
pull: the manager's poll of the version key, not transport or swap."""

import statistics


def read(records):
    waits = (records.get("bumps") or {}).get("poll_wait_s") or []
    return statistics.median(waits) if waits else None
