"""Key blocks the train step's windowed attention calls at window 2048 visit over the key blocks a causal kernel would visit at the same tile; from the program's trace-time count."""

from benchmark import window_trace


def read(records):
    return window_trace.window_blocks_visited_pct(records)
