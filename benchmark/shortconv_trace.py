"""The traced run seen through an LFM2-MoE block's own names — what the
per-layer metrics ``shortconv_*`` and ``lfm2_*`` read: device self time
per scope of the short-convolution mixer (``shortconv_in_proj``,
``shortconv``, ``shortconv_out_proj``:
``areal_tpu/base/telemetry.SHORTCONV_SCOPES``) and of the leading dense
block's FFN (``mlp``), read from the same trace file the same way as
``gdn_trace`` reads its scopes; the convolutions and the attention calls
the traced steps ran, by the packer's documents, and the program's gauge
of cut taps from the driver's records; operations and bytes from
``shortconv_cost``. The expert layer's metrics (``lfm2_experts_*``,
``lfm2_route_*``, ``lfm2_local_rows_pct``) read what the Trinity cell's
read (``moe_trace``, ``afmoe_trace.experts_roofline``, ``window_trace``).
A program without these scopes or counters (the parent commit) gives None
and the metric leaves the line. No jax.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmark import gdn_trace, peaks, shortconv_cost, window_trace
from benchmark import program_trace as pt

CONV_SCOPES = ("shortconv_in_proj", "shortconv", "shortconv_out_proj")
SCOPES = CONV_SCOPES + ("mlp",)

_LOADED: Dict[str, Dict[str, Any]] = {}


def load(records: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    if not records.get("trace"):
        return None
    path = pt.newest_trace()
    if path is None:
        return None
    if path not in _LOADED:
        planes, _ = pt.read_xplane(path)
        _LOADED[path] = gdn_trace.reduce_planes(
            planes, pt.read_framework_names(path), SCOPES)
    return _LOADED[path] or None


def scope_seconds(records, *scopes: str) -> Optional[float]:
    """Seconds under ``scopes``; None where the trace holds none of the
    mixer's scopes (a program that has no such block)."""
    red = load(records)
    if not red or not any(s in red["scopes"] for s in CONV_SCOPES):
        return None
    return sum(red["scopes"].get(s, 0.0) for s in scopes)


# ---- what the metric files under metrics/ call ----

def scope_busy_pct(records, *scopes: str) -> Optional[float]:
    secs = scope_seconds(records, *scopes)
    return None if secs is None else 100.0 * secs / load(records)["busy_s"]


def conv_roofline(records) -> Optional[float]:
    """Least time by the chip's peaks (its HBM bandwidth binds) for the
    doubly gated convolutions the traced steps NEEDED
    (``shortconv_calls_traced``: per packed grid, one a short-convolution
    block a pass — the inference forward, the train forward and one
    backward; a forward that a remat re-runs is the implementation's and
    is not counted) over the device time of scope ``shortconv``."""
    secs = scope_seconds(records, "shortconv")
    calls = (records.get("counters") or {}).get("shortconv_calls_traced")
    if not secs or not calls:
        return None
    kind = records["device"]["kind"]
    least = sum(
        n * peaks.least_time(*shortconv_cost.glue_cost(
            c["rows"], c["length"], c["channels"], c["taps"], backward),
            kind)[0]
        for c in calls
        for n, backward in ((c["fwd"], False), (c["bwd"], True)))
    return 100.0 * least / secs


def attn_roofline(records) -> Optional[float]:
    """Least time by the chip's peaks for the causal attention the traced
    steps ran at heads of 64, a DOCUMENT at a time
    (``lfm2_attn_calls_traced``: per micro-batch layout, its documents'
    lengths and the calls an attention block made over it — the re-run
    forward left out where the grid's grad program kept the kernel's
    output) over the grouped-head kernels' own time
    (``window_trace.window_times``: the device ops by name)."""
    wt = window_trace.window_times(records)
    calls = (records.get("counters") or {}).get("lfm2_attn_calls_traced")
    if wt is None or not calls:
        return None
    cfg, kind = records["config"], records["device"]["kind"]
    least = sum(
        n * peaks.least_time(*shortconv_cost.attention_cost(
            cfg, c["documents"], backward), kind)[0]
        for c in calls
        for n, backward in ((c["fwd"], False), (c["bwd"], True)))
    return 100.0 * least / sum(wt.values())


def resets_per_row(records) -> Optional[float]:
    """The program's gauge ``train/shortconv_resets_per_row``, averaged
    over the window's train batches; None where it has no such gauge."""
    return (records.get("counters") or {}).get("shortconv_resets_per_row")
