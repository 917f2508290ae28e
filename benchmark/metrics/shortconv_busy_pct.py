"""Device self time under the scope `shortconv` (a short-convolution block's two gates and the taps between them, forward and backward) over busy time."""

from benchmark import shortconv_trace


def read(records):
    return shortconv_trace.scope_busy_pct(records, "shortconv")
