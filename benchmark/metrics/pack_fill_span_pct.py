"""`real_tokens` over `padded_tokens` of the `areal/train/upload` spans of the traced steps."""

from benchmark import program_trace


def read(records):
    return program_trace.span_fill_pct(records, "train/upload")
