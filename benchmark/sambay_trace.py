"""The traced run seen through a decoder-hybrid-decoder model's own names
(``model_type`` phi4flash) — what the per-layer metrics ``sambay_*`` read:
device self time per scope of the S6 mixer (``s6_in_proj``, ``s6_conv``,
``s6_xdt_proj``, ``s6_scan``, ``s6_out_proj``), of a gated memory unit
(``gmu``), of a cross-attention layer (``cross_attention``: its two
projections AND the flash kernel over another layer's K/V) and of
differential attention's lambda-combine with its sub-norm
(``diff_attn_combine``) — ``areal_tpu/base/telemetry.SAMBAY_SCOPES`` —
read from the same trace file the same way as ``ssm_trace`` reads its
scopes; the windowed kernel by op name and the program's trace-time count
as ``window_trace`` reads them; the scan's operations and bytes from
``sambay_cost``; the calls the traced steps ran from the driver's
records. A program without these scopes or counters (the parent commit)
gives None and the metric leaves the line. No jax.
"""

from __future__ import annotations

import bisect
from typing import Any, Dict, Optional

from benchmark import peaks, sambay_cost, window_trace
from benchmark import program_trace as pt
from benchmark.trace_reduce import DEVICE_PLANE, OPS_LINE, _union

S6_SCOPES = ("s6_in_proj", "s6_conv", "s6_xdt_proj", "s6_scan",
             "s6_out_proj")
SCOPES = S6_SCOPES + ("gmu", "cross_attention", "diff_attn_combine")


def scope_of(framework_name: str) -> Optional[str]:
    """The innermost name of ``SCOPES`` in an op's framework name."""
    first = framework_name.split(";")[0].split(":")[0]
    for part in reversed(first.split("/")):
        while True:
            m = pt.WRAPPER.match(part)
            if not m:
                break
            part = m.group(1)
        if part in SCOPES:
            return part
    return None


def reduce_planes(planes, framework_names) -> Dict[str, Any]:
    """{"busy_s", "scopes": {scope: s}}; seconds per chip (the mean over
    the device planes), as ``ssm_trace.reduce_planes``."""
    chips: Dict[int, Dict[str, list]] = {}
    for pl in planes:
        m = DEVICE_PLANE.match(pl["name"])
        for ln in pl["lines"]:
            if m and ln["name"] in (OPS_LINE, pt.MODULES_LINE):
                chips.setdefault(int(m.group(1)), {}).setdefault(
                    ln["name"], []).extend(ln["events"])
    chips = {c: d for c, d in chips.items() if d.get(OPS_LINE)}
    if not chips or framework_names is None:
        return {}
    n = len(chips)
    scopes: Dict[str, float] = {}
    busy = 0.0
    for d in chips.values():
        modules = sorted(d.get(pt.MODULES_LINE, []))
        starts = [s for s, _, _ in modules]
        for secs, (s, name) in pt._event_self_times(
                [(s, e, (s, nm)) for s, e, nm in d[OPS_LINE]]):
            i = bisect.bisect_right(starts, s) - 1
            inside = i >= 0 and s < modules[i][1]
            pid = pt.program_of(modules[i][2])[1] if inside else ""
            scope = scope_of(framework_names.get((pid, name), ""))
            if scope:
                scopes[scope] = scopes.get(scope, 0.0) + secs / n
        busy += sum(e - s for s, e in _union(
            [(s, e) for s, e, _ in d[OPS_LINE]])) / n
    return {"busy_s": busy, "scopes": scopes}


_LOADED: Dict[str, Dict[str, Any]] = {}


def load(records: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    if not records.get("trace"):
        return None
    path = pt.newest_trace()
    if path is None:
        return None
    if path not in _LOADED:
        planes, _ = pt.read_xplane(path)
        _LOADED[path] = reduce_planes(planes, pt.read_framework_names(path))
    return _LOADED[path] or None


def scope_seconds(records, *scopes: str) -> Optional[float]:
    """Seconds under ``scopes``; None where the trace holds none of them
    (a program that has no such scope)."""
    red = load(records)
    if not red or not any(s in red["scopes"] for s in scopes):
        return None
    return sum(red["scopes"].get(s, 0.0) for s in scopes)


# ---- what the metric files under metrics/ call ----

def scope_busy_pct(records, *scopes: str) -> Optional[float]:
    secs = scope_seconds(records, *scopes)
    return None if secs is None else 100.0 * secs / load(records)["busy_s"]


def scan_roofline(records) -> Optional[float]:
    """Least time by the chip's peaks for the selective scans the traced
    steps ran (``s6_calls_traced``: per packed grid, one scan a Mamba
    layer a pass — forward in the inference pass; in the train pass
    forward, the forward a checkpointed layer re-runs, the forward the
    backward kernel re-runs inside itself, and a backward) over the device
    time of scope ``s6_scan``."""
    secs = scope_seconds(records, "s6_scan")
    calls = (records.get("counters") or {}).get("s6_calls_traced")
    if not secs or not calls:
        return None
    kind = records["device"]["kind"]
    least = 0.0
    for call in calls:  # {rows, length, d_inner, state, fwd, bwd}
        for n, backward in ((call["fwd"], False), (call["bwd"], True)):
            ops, nbytes = sambay_cost.selective_scan_cost(
                call["rows"], call["length"], call["d_inner"], call["state"],
                backward)
            least += n * peaks.least_time(ops, nbytes, kind)[0]
    return 100.0 * least / secs


def window_roofline(records) -> Optional[float]:
    """``window_trace.window_attn_roofline`` for a configuration whose
    head size has no key (hidden / heads): least time by the chip's peaks
    for the traced steps' windowed calls at the published 40 / 20 heads of
    64 over the kernels' time. A differential call multiplies its scores
    by a value of 128, which ``window_attention_cost`` counts at 64: the
    share is UNDER-stated by up to a third, never over."""
    wt, c = window_trace.window_times(records), records.get("counters") or {}
    calls = c.get("window_calls_traced")
    if wt is None or not calls:
        return None
    cfg, kind = records["config"], records["device"]["kind"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    least = 0.0
    for call in calls:  # {rows, length, window, tile, fwd, bwd}
        for n, backward in ((call["fwd"], False), (call["bwd"], True)):
            ops, nbytes = window_trace.window_attention_cost(
                call["rows"], call["length"], call["window"], call["tile"],
                nq, nkv, cfg["hidden_size"] // nq, backward)
            least += n * peaks.least_time(ops, nbytes, kind)[0]
    return 100.0 * least / sum(wt.values())
