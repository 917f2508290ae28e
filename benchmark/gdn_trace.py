"""The traced run seen through a Qwen3-Next block's own names — what the
per-layer metrics ``gdn_*`` and ``qnext_attn_*`` read: device self time
per scope of the Gated DeltaNet mixer (``gdn_in_proj``, ``gdn_conv``,
``gdn_gates``, ``gdn_rule``, ``gdn_gate_norm``, ``gdn_out_proj``:
``areal_tpu/base/telemetry.GDN_SCOPES``), read from the same trace file
the same way as ``ssm_trace`` reads its scopes; the rules and the
attention calls the traced steps ran and the program's gauge of document
starts inside a chunk from the driver's records; operations and bytes from
``gdn_cost``. The expert layer's metrics (``qnext_experts_*``,
``qnext_route_*``, ``qnext_shared_expert_*``, ``qnext_local_rows_pct``)
read what the Mellum cell's read (``moe_trace``, ``ssm_trace``,
``window_trace``). A program without these scopes or counters (the parent
commit) gives None and the metric leaves the line. No jax.
"""

from __future__ import annotations

import bisect
from typing import Any, Dict, Optional, Sequence

from benchmark import gdn_cost, peaks, window_trace
from benchmark import program_trace as pt
from benchmark.trace_reduce import DEVICE_PLANE, OPS_LINE, _union

SCOPES = ("gdn_in_proj", "gdn_conv", "gdn_gates", "gdn_rule",
          "gdn_gate_norm", "gdn_out_proj")


def scope_of(framework_name: str, scopes: Sequence[str]) -> Optional[str]:
    """The innermost name of ``scopes`` in an op's framework name."""
    first = framework_name.split(";")[0].split(":")[0]
    for part in reversed(first.split("/")):
        while True:
            m = pt.WRAPPER.match(part)
            if not m:
                break
            part = m.group(1)
        if part in scopes:
            return part
    return None


def reduce_planes(planes, framework_names, scopes: Sequence[str] = SCOPES,
                  ) -> Dict[str, Any]:
    """{"busy_s", "scopes": {scope: s}} for the names in ``scopes``;
    seconds per chip (the mean over the device planes), as
    ``ssm_trace.reduce_planes``."""
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
    out: Dict[str, float] = {}
    busy = 0.0
    for d in chips.values():
        modules = sorted(d.get(pt.MODULES_LINE, []))
        starts = [s for s, _, _ in modules]
        for secs, (s, name) in pt._event_self_times(
                [(s, e, (s, nm)) for s, e, nm in d[OPS_LINE]]):
            i = bisect.bisect_right(starts, s) - 1
            inside = i >= 0 and s < modules[i][1]
            pid = pt.program_of(modules[i][2])[1] if inside else ""
            scope = scope_of(framework_names.get((pid, name), ""), scopes)
            if scope:
                out[scope] = out.get(scope, 0.0) + secs / n
        busy += sum(e - s for s, e in _union(
            [(s, e) for s, e, _ in d[OPS_LINE]])) / n
    return {"busy_s": busy, "scopes": out}


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


def _least_seconds(calls, kind: str, cost) -> float:
    """Least time by the chip's peaks for ``calls`` ({..., fwd, bwd} each):
    ``cost(call, backward)`` gives one call's (operations, bytes)."""
    return sum(n * peaks.least_time(*cost(call, backward), kind)[0]
               for call in calls
               for n, backward in ((call["fwd"], False), (call["bwd"], True)))


def rule_roofline(records) -> Optional[float]:
    """Least time by the chip's peaks for the rules the traced steps ran
    (``gdn_rule_calls_traced``: per packed grid, one rule a Gated DeltaNet
    block a pass — the inference forward, the train forward and the
    forward its backward re-runs, and a backward; each call with its own
    geometry) over the device time of scope ``gdn_rule``."""
    secs = scope_seconds(records, "gdn_rule")
    calls = (records.get("counters") or {}).get("gdn_rule_calls_traced")
    if not secs or not calls:
        return None
    return 100.0 * _least_seconds(
        calls, records["device"]["kind"],
        lambda c, backward: gdn_cost.gdn_rule_cost(
            c["rows"], c["length"], c["k_heads"], c["v_heads"], c["dk"],
            c["dv"], backward)) / secs


def attn_roofline(records) -> Optional[float]:
    """Least time by the chip's peaks for the causal attention calls the
    traced steps ran at heads of 256 (``qnext_attn_calls_traced``: per
    packed grid, one call an attention block a pass; the re-run forward
    left out where the grid's grad program kept the kernel's output) over
    the grouped-head kernels' own time (``window_trace.window_times``:
    the device ops by name)."""
    wt = window_trace.window_times(records)
    calls = (records.get("counters") or {}).get("qnext_attn_calls_traced")
    if wt is None or not calls:
        return None
    cfg = records["config"]
    return 100.0 * _least_seconds(
        calls, records["device"]["kind"],
        lambda c, backward: gdn_cost.attention_cost(
            cfg, c["rows"], c["length"], backward)) / sum(wt.values())


def resets_in_chunk_per_row(records) -> Optional[float]:
    """The program's gauge ``train/gdn_resets_in_chunk_per_row``, averaged
    over the window's train batches; None where it has no such gauge."""
    return (records.get("counters") or {}).get("gdn_resets_in_chunk_per_row")
