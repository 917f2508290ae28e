"""Key blocks the train step's windowed calls visit over those a causal kernel would, from the program's trace-time count."""

from benchmark import window_trace


def read(records):
    return window_trace.window_blocks_visited_pct(records)
