"""Device self time under the scope `grad_accum` (scaling a micro-batch's gradients and adding them to the carry) over busy time."""

from benchmark import program_trace


def read(records):
    return program_trace.scope_busy_pct(records, "grad_accum")
