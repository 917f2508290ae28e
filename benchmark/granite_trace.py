"""The traced run seen through a Granite 4.0-H block's own names — what
the per-layer metrics ``granite_*`` read: device self time per scope of
the Mamba-2 mixer inside a whole block (``ssm_in_proj``, ``ssm_conv``,
``ssm_scan``, ``ssm_gate_norm``, ``ssm_out_proj``:
``areal_tpu/base/telemetry.SSM_SCOPES``), read from the same trace file
the same way as ``ssm_trace`` reads them for a mixer that is a layer by
itself; the scans the traced steps ran and the packer's documents per row
from the driver's records. A program without these scopes or counters
(the parent commit) gives None and the metric leaves the line. No jax.
"""

from __future__ import annotations

from typing import Optional

from benchmark import peaks, ssm_cost, ssm_trace

SCOPES = ssm_trace.SSM_SCOPES


scope_busy_pct = ssm_trace.scope_busy_pct


def scan_roofline(records) -> Optional[float]:
    """Least time by the chip's peaks for the scans the traced steps ran
    (``granite_scan_calls_traced``: per packed grid, one scan a Mamba
    block a pass — the inference forward, the train forward and the
    forward its backward re-runs, and a backward; each call with its own
    geometry) over the device time of scope ``ssm_scan``."""
    secs = ssm_trace.scope_seconds(records, "ssm_scan")
    calls = (records.get("counters") or {}).get("granite_scan_calls_traced")
    if not secs or not calls:
        return None
    kind = records["device"]["kind"]
    least = 0.0
    for call in calls:  # {rows, length, chunk, heads, head_dim, groups,
        #                  state, fwd, bwd}
        for n, backward in ((call["fwd"], False), (call["bwd"], True)):
            ops, nbytes = ssm_cost.ssd_scan_cost(
                call["rows"], call["length"], call["chunk"], call["heads"],
                call["head_dim"], call["groups"], call["state"], backward)
            least += n * peaks.least_time(ops, nbytes, kind)[0]
    return 100.0 * least / secs


def docs_per_row(records) -> Optional[float]:
    """The program's gauge ``train/docs_per_row``, averaged over the
    window's train batches; None where the program has no such gauge."""
    return (records.get("counters") or {}).get("docs_per_row")
