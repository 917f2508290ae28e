"""The traced run seen through the expert layer's own names: device self
time per scope inside ``moe`` (``moe_router``, ``moe_dispatch``,
``moe_exchange``, ``moe_experts`` — ``areal_tpu/base/telemetry.MOE_SCOPES``;
the grouped GEMMs by their op name) and per kind of collective op, per chip (the mean over the device planes),
as ``program_trace`` gives the outer scopes. Reads the same trace file the
same way (the run's newest ``*.xplane.pb``; op → framework name through
xprof's ``hlo_stats``), once per process. A program without these scopes,
or an image without xprof, gives None and the metrics leave the line.
"""

from __future__ import annotations

import bisect
import re
from typing import Any, Dict, Optional

from benchmark import program_trace as pt
from benchmark.trace_reduce import DEVICE_PLANE, OPS_LINE, _union, base_name

MOE_SCOPES = ("moe_router", "moe_dispatch", "moe_exchange", "moe_experts")
# The grouped GEMMs themselves: the TPU compiler rewrites ``ragged_dot``
# into custom calls named ``ragged-dot-none`` that keep no framework name
# (seen in the compiled program's text and on the chip's op line, PR 26),
# so they are known by their op name, as the flash kernels are, and count
# as ``moe_experts``. A Pallas grouped GEMM would be named here too.
EXPERT_GEMM = re.compile(r"^(ragged-dot|gmm|tgmm)")
# HLO collectives as the device's op line names them (async pairs carry
# -start / -done; a fused reduce-scatter is an all-reduce-scatter fusion).
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast|ragged-all-to-all|all-reduce-scatter)")


def moe_scope_of(framework_name: str) -> Optional[str]:
    """The innermost ``moe_*`` name in an op's framework name."""
    first = framework_name.split(";")[0].split(":")[0]
    for part in reversed(first.split("/")):
        while True:
            m = pt.WRAPPER.match(part)
            if not m:
                break
            part = m.group(1)
        if part in MOE_SCOPES:
            return part
    return None


def collective_kind(op: str) -> Optional[str]:
    m = COLLECTIVE.match(op)
    return m.group(1) if m else None


def reduce_planes(planes, framework_names) -> Dict[str, Any]:
    """{"busy_s", "scopes": {moe scope: s} or None, "collectives": {kind:
    s}}; seconds per chip. ``planes`` and ``framework_names`` as
    ``program_trace.reduce_planes`` takes them."""
    chips: Dict[int, Dict[str, list]] = {}
    for pl in planes:
        m = DEVICE_PLANE.match(pl["name"])
        for ln in pl["lines"]:
            if m and ln["name"] in (OPS_LINE, pt.MODULES_LINE):
                chips.setdefault(int(m.group(1)), {}).setdefault(
                    ln["name"], []).extend(ln["events"])
    chips = {c: d for c, d in chips.items() if d.get(OPS_LINE)}
    if not chips:
        return {}
    n = len(chips)
    scopes: Dict[str, float] = {}
    coll: Dict[str, float] = {}
    busy = 0.0
    for d in chips.values():
        modules = sorted(d.get(pt.MODULES_LINE, []))
        starts = [s for s, _, _ in modules]
        for secs, (s, name) in pt._event_self_times(
                [(s, e, (s, nm)) for s, e, nm in d[OPS_LINE]]):
            kind = collective_kind(base_name(name))
            if kind:
                coll[kind] = coll.get(kind, 0.0) + secs / n
            if framework_names is None:
                continue
            i = bisect.bisect_right(starts, s) - 1
            inside = i >= 0 and s < modules[i][1]
            pid = pt.program_of(modules[i][2])[1] if inside else ""
            scope = ("moe_experts" if EXPERT_GEMM.match(base_name(name))
                     else moe_scope_of(framework_names.get((pid, name), "")))
            if scope:
                scopes[scope] = scopes.get(scope, 0.0) + secs / n
        busy += sum(e - s for s, e in _union(
            [(s, e) for s, e, _ in d[OPS_LINE]])) / n
    return {"busy_s": busy, "scopes": scopes or None, "collectives": coll}


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


# ---- what the metric files under metrics/ call ----

def scope_busy_pct(records, *scopes: str) -> Optional[float]:
    red = load(records)
    if not red or red["scopes"] is None:
        return None
    return 100.0 * sum(red["scopes"].get(s, 0.0)
                       for s in scopes) / red["busy_s"]


def collective_busy_pct(records) -> Optional[float]:
    """Self time of every collective op over device busy time; None on a
    trace with no collective (one chip)."""
    red = load(records)
    if not red or not red["collectives"]:
        return None
    return 100.0 * sum(red["collectives"].values()) / red["busy_s"]


def experts_roofline(records) -> Optional[float]:
    """Least time the chip's peaks allow for the traced steps' grouped
    GEMMs over the device time of ``moe_experts`` (the GEMM ops and the
    elementwise ops of the scope between them). The work is what the traced steps
    routed (``moe_routed_rows`` of each step's statistics: per layer, over
    the mesh), a chip's share of it, in the passes a step makes: the
    inference forward, the train forward and its recomputation under full
    remat, and one backward."""
    from benchmark import moe_cost, peaks

    red = load(records)
    c = records.get("counters") or {}
    if (not red or red["scopes"] is None
            or not red["scopes"].get("moe_experts")
            or not c.get("moe_routed_rows_traced")):
        return None
    cfg, kind = records["config"], records["device"]["kind"]
    chips, layers = records["chips"], cfg["num_hidden_layers"]
    rows = c["moe_routed_rows_traced"] * layers / chips
    calls = c["moe_mbs_traced"] * layers
    groups = cfg["num_experts"] // chips
    d, f = cfg["hidden_size"], moe_cost.expert_width(cfg)
    least = 0.0
    for passes, backward in ((3, False), (1, True)):
        ops, nbytes = moe_cost.grouped_ffn_cost(
            passes * rows, passes * calls, groups, d, f, backward)
        least += peaks.least_time(ops, nbytes, kind)[0]
    return 100.0 * least / red["scopes"]["moe_experts"]
