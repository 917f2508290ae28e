"""Largest expert's share of the routed (token, expert) pairs over the mean share (1 = balanced), mean over the window's steps; from the steps' statistics."""


def read(records):
    return (records.get("counters") or {}).get("moe_expert_load_ratio")
