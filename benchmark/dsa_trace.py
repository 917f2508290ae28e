"""The traced run seen through a block under a learned selection — what
the per-layer metrics ``dsa_*`` read: device self time per scope of
``areal_tpu/base/telemetry.DSA_SCOPES`` (``dsa_index_proj``,
``dsa_index_scores``, ``dsa_select``, ``dsa_attention``), read from the
same trace file the same way as ``gdn_trace`` reads its scopes; the calls
the traced steps ran, by the packer's documents, and the program's counts
of selected and causal pairs, from the driver's records; operations and
bytes from ``dsa_cost``. Three of them are entries of BENCHMARK.json
(``metrics/dsa_*.py``); the other four (:func:`readings`) wait for room
there — its ``per_layer`` list is full at the 128 entries the driver's
check of the file allows — and go into a traced run's notes. The cell's
projections and expert layer are read by metrics the benchmark had
(``attn_proj_busy_pct``, ``share_*``). A program without these scopes or
counters (the parent commit) gives None and the metric leaves the line.
No jax.

The program makes a tile's scores INSIDE the kernels of ``dsa_select`` and
``dsa_attention`` (no [T, S] array reaches HBM), so the indexer's roofline
is held against the time of ``dsa_index_proj`` + ``dsa_index_scores`` +
``dsa_select`` — the scopes that hold ONE scoring a forward — and the
selection's against ``dsa_select`` alone.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmark import dsa_cost, gdn_trace, peaks
from benchmark import program_trace as pt

DSA_SCOPES = ("dsa_index_proj", "dsa_index_scores", "dsa_select",
              "dsa_attention")
INDEX_SCOPES = ("dsa_index_proj", "dsa_index_scores")

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
        _LOADED[path] = gdn_trace.reduce_planes(planes, names, DSA_SCOPES)
    return _LOADED[path] or None


def scope_seconds(records, *scopes: str) -> Optional[float]:
    """Seconds under ``scopes``; None where the trace holds none of the
    selection's scopes (a program that has no such block)."""
    red = load(records)
    if not red or not any(s in red["scopes"] for s in DSA_SCOPES):
        return None
    return sum(red["scopes"].get(s, 0.0) for s in scopes)


def _calls(records, name: str):
    return (records.get("counters") or {}).get(name)


def scope_busy_pct(records, *scopes: str) -> Optional[float]:
    secs = scope_seconds(records, *scopes)
    return None if secs is None else 100.0 * secs / load(records)["busy_s"]


def _least(records, cost) -> Optional[float]:
    """Least seconds by the chip's peaks for the calls the traced steps
    ran (``dsa_calls_traced``: per micro-batch layout its documents and
    ``fwd`` / ``bwd`` calls a block, ``scorings`` the forwards the step
    needs)."""
    calls = _calls(records, "dsa_calls_traced")
    if not calls:
        return None
    kind = records["device"]["kind"]
    return sum(n * peaks.least_time(*c, kind)[0]
               for call in calls for n, c in cost(call))


# ---- what the metric files under metrics/ call ----

def attn_busy_pct(records) -> Optional[float]:
    return scope_busy_pct(records, "dsa_attention")


def select_busy_pct(records) -> Optional[float]:
    return scope_busy_pct(records, "dsa_select")


def index_busy_pct(records) -> Optional[float]:
    return scope_busy_pct(records, *INDEX_SCOPES)


def attn_roofline(records) -> Optional[float]:
    """Least time by the chip's peaks for attention over the SELECTED
    pairs of the traced steps' documents (forward, the forward a grid's
    grad program re-runs, backward 2.5 x: ``dsa_cost.attention_cost``)
    over the device time of scope ``dsa_attention`` — so a sweep of every
    causal block under a mask reads as the fraction it is."""
    secs = scope_seconds(records, "dsa_attention")
    cfg = records.get("config") or {}
    if not secs or "sa_config" not in cfg:
        return None
    least = _least(records, lambda c: (
        (c["fwd"], dsa_cost.attention_cost(cfg, c["documents"], False)),
        (c["bwd"], dsa_cost.attention_cost(cfg, c["documents"], True))))
    return None if least is None else 100.0 * least / secs


def index_roofline(records) -> Optional[float]:
    """Least time for ONE scoring a forward the step needs (the inference
    pass, the train pass: ``scorings``) over the scopes that hold it."""
    secs = scope_seconds(records, *INDEX_SCOPES, "dsa_select")
    cfg = records.get("config") or {}
    if not secs or "sa_config" not in cfg:
        return None
    least = _least(records, lambda c: (
        (c["scorings"], dsa_cost.index_cost(cfg, c["documents"])),))
    return None if least is None else 100.0 * least / secs


def select_roofline(records) -> Optional[float]:
    secs = scope_seconds(records, "dsa_select")
    cfg = records.get("config") or {}
    if not secs or "sa_config" not in cfg:
        return None
    least = _least(records, lambda c: (
        (c["scorings"], dsa_cost.select_cost(cfg, c["documents"])),))
    return None if least is None else 100.0 * least / secs


def selected_pct(records) -> Optional[float]:
    """Of the causal same-document pairs of the window's train steps, the
    share the attention let through (the device's own counts)."""
    sel, causal = (_calls(records, k) for k in (
        "dsa_selected_pairs", "dsa_causal_pairs"))
    return 100.0 * sel / causal if sel and causal else None


def readings(records) -> Dict[str, Optional[float]]:
    """The readings BENCHMARK.json has no entry for, for the driver's
    notes."""
    return {
        "dsa_index_busy_pct": index_busy_pct(records),
        "dsa_index_roofline": index_roofline(records),
        "dsa_select_roofline": select_roofline(records),
        "dsa_selected_pct": selected_pct(records),
    }
