"""Device self time under the scope `param_cast` (f32 masters to the compute dtype, once a program call) over busy time."""

from benchmark import program_trace


def read(records):
    return program_trace.scope_busy_pct(records, "param_cast")
