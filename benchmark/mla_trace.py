"""The traced run seen through a GLM-4.7-Flash block's own names — what
the per-layer metrics ``mla_*`` and ``glm_*`` read: device self time per
scope of the latent projection path (``mla_q_proj``, ``mla_kv_down``,
``mla_kv_up``, ``mla_assemble``: ``areal_tpu/base/telemetry.MLA_SCOPES``),
of ``o_proj``, of the leading dense block's FFN (``mlp``) and of the shared
expert (``shared_expert``), read from the same trace file the same way as
``gdn_trace`` reads its scopes; the projection paths and the attention
calls the traced steps ran, by the packer's grids and documents, from the
driver's records; operations and bytes from ``mla_cost``. The kernels' and
the routed experts' busy shares and the share's local rows read what the
LFM2 cell's read (``window_trace``, ``moe_trace``: their metric files call
those). A program without these scopes or counters (the parent
commit) gives None and the metric leaves the line. No jax.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmark import gdn_trace, mla_cost, moe_cost, moe_trace, peaks
from benchmark import program_trace as pt
from benchmark import window_trace

MLA_SCOPES = ("mla_q_proj", "mla_kv_down", "mla_kv_up", "mla_assemble")
PROJ_SCOPES = ("mla_q_proj", "mla_kv_down", "mla_kv_up", "o_proj")
SCOPES = MLA_SCOPES + ("o_proj", "mlp", "shared_expert")

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
    projection path's scopes (a program that has no such block)."""
    red = load(records)
    if not red or not any(s in red["scopes"] for s in MLA_SCOPES):
        return None
    return sum(red["scopes"].get(s, 0.0) for s in scopes)


def _calls(records, name: str):
    return (records.get("counters") or {}).get(name)


# ---- what the metric files under metrics/ call ----

def scope_busy_pct(records, *scopes: str) -> Optional[float]:
    secs = scope_seconds(records, *scopes)
    return None if secs is None else 100.0 * secs / load(records)["busy_s"]


def proj_roofline(records) -> Optional[float]:
    """Least time by the chip's bf16 peak for the five projections the
    traced steps NEEDED (``mla_calls_traced``: per packed grid, one path a
    block a pass — the inference forward, the train forward and one
    backward; a forward that a remat re-runs is the implementation's and
    is not counted) over the device time of their scopes."""
    secs = scope_seconds(records, *PROJ_SCOPES)
    calls = _calls(records, "mla_calls_traced")
    if not secs or not calls:
        return None
    cfg, kind = records["config"], records["device"]["kind"]
    least = sum(
        n * peaks.least_time(mla_cost.projection_cost(
            cfg, c["rows"] * c["length"], backward), 0.0, kind)[0]
        for c in calls
        for n, backward in ((c["fwd"], False), (c["bwd"], True)))
    return 100.0 * least / secs


def assemble_roofline(records) -> Optional[float]:
    """Least time by the chip's peaks (its HBM bandwidth binds) for the
    assemblies the traced steps NEEDED (as :func:`proj_roofline` counts
    them; ``mla_cost.assemble_cost``) over the device time of scope
    ``mla_assemble``."""
    secs = scope_seconds(records, "mla_assemble")
    calls = _calls(records, "mla_calls_traced")
    if not secs or not calls:
        return None
    cfg, kind = records["config"], records["device"]["kind"]
    least = sum(
        n * peaks.least_time(*mla_cost.assemble_cost(
            cfg, c["rows"] * c["length"], backward), kind)[0]
        for c in calls
        for n, backward in ((c["fwd"], False), (c["bwd"], True)))
    return 100.0 * least / secs


def attn_roofline(records) -> Optional[float]:
    """Least time by the chip's peaks for the causal attention the traced
    steps ran at 20 / 20 heads of 256, a DOCUMENT at a time
    (``mla_attn_calls_traced``: per micro-batch layout, its documents'
    lengths and the calls the blocks made over it — the re-run forward
    left out where the grid's grad program kept the kernel's output) over
    the grouped-head kernels' own time (``window_trace.window_times``)."""
    wt = window_trace.window_times(records)
    calls = _calls(records, "mla_attn_calls_traced")
    if wt is None or not calls:
        return None
    cfg, kind = records["config"], records["device"]["kind"]
    least = sum(
        n * peaks.least_time(*mla_cost.attention_cost(
            cfg, c["documents"], backward), kind)[0]
        for c in calls
        for n, backward in ((c["fwd"], False), (c["bwd"], True)))
    return 100.0 * least / sum(wt.values())


def experts_roofline(records) -> Optional[float]:
    """Least time by the chip's peaks for the traced steps' grouped GEMMs
    over the held experts — the rows that landed here
    (``moe_local_rows_traced``, per expert layer) through experts of K
    ``hidden_size`` / N ``moe_intermediate_size``, ``n_routed_experts``
    (held) groups a call, on the EXPERT blocks, in the passes a step makes
    (three forwards and a backward, as ``afmoe_trace.experts_roofline``)
    — over the device time of the scope ``moe_experts``."""
    red = moe_trace.load(records)
    c = records.get("counters") or {}
    if (not red or red["scopes"] is None
            or not red["scopes"].get("moe_experts")
            or not c.get("moe_local_rows_traced")
            or not c.get("mla_calls_traced")):
        return None
    cfg, kind = records["config"], records["device"]["kind"]
    layers = mla_cost.layer_counts(cfg)["experts"]
    rows = c["moe_local_rows_traced"] * layers
    calls = c["moe_mbs_traced"] * layers
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    least = 0.0
    for passes, backward in ((3, False), (1, True)):
        ops, nbytes = moe_cost.grouped_ffn_cost(
            passes * rows, passes * calls, cfg["n_routed_experts"], d, f,
            backward)
        least += peaks.least_time(ops, nbytes, kind)[0]
    return 100.0 * least / red["scopes"]["moe_experts"]
