"""Device idle time while the host was inside an `areal/*/split_pack` or `areal/*/upload` span, over the traced window."""

from benchmark import program_trace


def read(records):
    return program_trace.span_idle_pct(records, "/split_pack", "/upload")
