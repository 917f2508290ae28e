"""Device idle time while the host was inside `areal/infer/fetch` or `areal/train/fetch_stats`, over the traced window."""

from benchmark import program_trace


def read(records):
    return program_trace.span_idle_pct(records, "infer/fetch", "train/fetch_stats")
