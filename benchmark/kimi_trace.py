"""The traced run seen through a Kimi-Linear block's own names — what the
per-layer metrics ``kda_*`` and ``kimi_*`` read: device self time per scope
of the Kimi Delta Attention mixer (``kda_in_proj``, ``kda_conv``,
``kda_gates``, ``kda_rule``, ``kda_gate_norm``, ``kda_out_proj``:
``areal_tpu/base/telemetry.KDA_SCOPES``) and of the latent projection path
(``MLA_SCOPES`` and ``o_proj``), read from the same trace file the same way
as ``gdn_trace`` reads its scopes; the rules and the attention calls the
traced steps ran, by the packer's grids and documents, and the program's
gauge of document starts inside a chunk, from the driver's records;
operations and bytes from ``kda_cost``. The expert layer's busy shares and
the share's local rows read what the GLM cell's read (``moe_trace``,
``window_trace``). A program without these scopes or counters (the parent
commit) gives None and the metric leaves the line. No jax.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Optional

from benchmark import gdn_trace, kda_cost, moe_cost, moe_trace, peaks
from benchmark import program_trace as pt
from benchmark import window_trace
from benchmark.mla_trace import MLA_SCOPES, PROJ_SCOPES

KDA_SCOPES = ("kda_in_proj", "kda_conv", "kda_gates", "kda_rule",
              "kda_gate_norm", "kda_out_proj")
KDA_PROJ_SCOPES = ("kda_in_proj", "kda_out_proj")
KDA_GLUE_SCOPES = ("kda_conv", "kda_gates", "kda_gate_norm")
SCOPES = KDA_SCOPES + MLA_SCOPES + ("o_proj",)
# The grouped-head kernels' own ops under the scope ``causal_attention``:
# ``splash_*`` by name on a one-row grid; on a grid of several rows the
# call is batched over the rows and reaches the trace as ``closed_call.N``
# (2 x 7,552 in this cell: half of the kernels' time, which
# ``window_trace.WINDOW_OP`` does not match).
ATTN_SCOPE = "causal_attention"
ATTN_KERNEL_OP = re.compile(r"^(splash_|closed_call)")

_LOADED: Dict[str, Dict[str, Any]] = {}


def load(records: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    if not records.get("trace"):
        return None
    path = pt.newest_trace()
    if path is None:
        return None
    if path not in _LOADED:
        planes, _ = pt.read_xplane(path)
        names = pt.read_framework_names(path)
        red = gdn_trace.reduce_planes(planes, names, SCOPES)
        if red:
            kernels = {k: v for k, v in names.items()
                       if ATTN_KERNEL_OP.match(k[1])}
            red["attn_kernel_s"] = gdn_trace.reduce_planes(
                planes, kernels, (ATTN_SCOPE,))["scopes"].get(ATTN_SCOPE)
        _LOADED[path] = red
    return _LOADED[path] or None


def scope_seconds(records, *scopes: str) -> Optional[float]:
    """Seconds under ``scopes``; None where the trace holds none of the
    KDA mixer's scopes (a program that has no such block)."""
    red = load(records)
    if not red or not any(s in red["scopes"] for s in KDA_SCOPES):
        return None
    return sum(red["scopes"].get(s, 0.0) for s in scopes)


def _calls(records, name: str):
    return (records.get("counters") or {}).get(name)


def attn_kernel_seconds(records) -> Optional[float]:
    """Seconds of the grouped-head kernels' own ops (``ATTN_KERNEL_OP``
    under ``ATTN_SCOPE``); where the trace names no such scope, the ops
    ``window_trace`` knows by name; None without either."""
    red = load(records)
    if red and red.get("attn_kernel_s"):
        return red["attn_kernel_s"]
    wt = window_trace.window_times(records)
    return sum(wt.values()) if wt else None


# ---- what the metric files under metrics/ call ----

def scope_busy_pct(records, *scopes: str) -> Optional[float]:
    secs = scope_seconds(records, *scopes)
    return None if secs is None else 100.0 * secs / load(records)["busy_s"]


def attn_busy_pct(records) -> Optional[float]:
    secs = attn_kernel_seconds(records)
    if secs is None:
        return None
    return 100.0 * secs / records["trace"]["busy_s"]


def mla_proj_busy_pct(records) -> Optional[float]:
    return scope_busy_pct(records, *PROJ_SCOPES)


def rule_roofline(records) -> Optional[float]:
    """Least time by the chip's peaks for the rules the traced steps ran
    (``kda_rule_calls_traced``: per packed grid, one rule a KDA block a
    pass — the inference forward, the train forward and the forward its
    backward re-runs, and a backward; ``kda_cost.kda_rule_cost`` of each)
    over the device time of scope ``kda_rule``."""
    secs = scope_seconds(records, "kda_rule")
    calls = _calls(records, "kda_rule_calls_traced")
    if not secs or not calls:
        return None
    return 100.0 * gdn_trace._least_seconds(
        calls, records["device"]["kind"],
        lambda c, backward: kda_cost.kda_rule_cost(
            c["rows"], c["length"], c["heads"], c["dk"], c["dv"],
            backward)) / secs


def attn_roofline(records) -> Optional[float]:
    """Least time by the chip's peaks for the causal attention the traced
    steps ran at 32 / 32 heads of 192 over a value of 128, a DOCUMENT at a
    time (``kimi_attn_calls_traced``: per micro-batch layout, its
    documents' lengths and the calls the attention blocks made over it —
    the re-run forward left out where the grid's grad program kept the
    kernel's output) over the grouped-head kernels' own time
    (:func:`attn_kernel_seconds`)."""
    secs = attn_kernel_seconds(records)
    calls = _calls(records, "kimi_attn_calls_traced")
    if not secs or not calls:
        return None
    cfg = records["config"]
    return 100.0 * gdn_trace._least_seconds(
        calls, records["device"]["kind"],
        lambda c, backward: kda_cost.attention_cost(
            cfg, c["documents"], backward)) / secs


def experts_roofline(records) -> Optional[float]:
    """Least time by the chip's peaks for the traced steps' grouped GEMMs
    over the held experts — the rows that landed here
    (``moe_local_rows_traced``, per expert layer) through experts of K
    ``hidden_size`` / N ``moe_intermediate_size``, ``num_experts`` (held)
    groups a call, on the EXPERT blocks, in the passes a step makes (three
    forwards and a backward, as ``mla_trace.experts_roofline``) — over the
    device time of the scope ``moe_experts``."""
    red = moe_trace.load(records)
    c = records.get("counters") or {}
    if (not red or red["scopes"] is None
            or not red["scopes"].get("moe_experts")
            or not c.get("moe_local_rows_traced")
            or not c.get("kda_rule_calls_traced")):
        return None
    cfg, kind = records["config"], records["device"]["kind"]
    layers = kda_cost.layer_counts(cfg)["experts"]
    rows = c["moe_local_rows_traced"] * layers
    calls = c["moe_mbs_traced"] * layers
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    least = 0.0
    for passes, backward in ((3, False), (1, True)):
        ops, nbytes = moe_cost.grouped_ffn_cost(
            passes * rows, passes * calls, cfg["num_experts"], d, f,
            backward)
        least += peaks.least_time(ops, nbytes, kind)[0]
    return 100.0 * least / red["scopes"]["moe_experts"]


def resets_in_chunk_per_row(records) -> Optional[float]:
    """The program's gauge ``train/kda_resets_in_chunk_per_row``, averaged
    over the window's train batches; None where it has no such gauge."""
    return _calls(records, "kda_resets_in_chunk_per_row")
