"""Device self time under scope dsa_select (the kernel dsa_select: a tile's scores and both bisections) over the traced window's busy time."""

from benchmark import dsa_trace


def read(records):
    return dsa_trace.select_busy_pct(records)
