"""The traced run seen through a hybrid model's own names — what the
per-layer metrics ``ssm_*``, ``latent_*`` and ``shared_expert_busy_pct``
read: device self time per scope of the state-space mixer (``ssm_in_proj``,
``ssm_conv``, ``ssm_scan``, ``ssm_gate_norm``, ``ssm_out_proj``:
``areal_tpu/base/telemetry.SSM_SCOPES``) and of the latent expert layer
(``moe_router``, ``moe_dispatch``, ``moe_experts`` with the grouped GEMMs
by op name, ``latent_down``, ``latent_up``, ``shared_expert``), per chip,
read from the same trace file the same way as ``moe_trace`` reads its
scopes; the scan's and the experts' operations and bytes from
``ssm_cost``; the calls the traced steps ran and the share's routing
counters from the driver's records. A program without these scopes or
counters (the parent commit) gives None and the metric leaves the line.
No jax.
"""

from __future__ import annotations

import bisect
from typing import Any, Dict, Optional

from benchmark import moe_trace, peaks, ssm_cost
from benchmark import program_trace as pt
from benchmark.trace_reduce import DEVICE_PLANE, OPS_LINE, _union, base_name

SSM_SCOPES = ("ssm_in_proj", "ssm_conv", "ssm_scan", "ssm_gate_norm",
              "ssm_out_proj")
LATENT_SCOPES = ("latent_down", "latent_up", "shared_expert")
SCOPES = SSM_SCOPES + LATENT_SCOPES + moe_trace.MOE_SCOPES


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
    the device planes), as ``moe_trace.reduce_planes``."""
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
            scope = ("moe_experts"
                     if moe_trace.EXPERT_GEMM.match(base_name(name))
                     else scope_of(framework_names.get((pid, name), "")))
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


def ssm_scan_roofline(records) -> Optional[float]:
    """Least time by the chip's peaks for the scans the traced steps ran
    (``ssm_calls_traced``: per packed grid, one scan a Mamba layer a pass
    — the inference forward, the train forward and the forward its
    backward re-runs, and a backward) over the device time of scope
    ``ssm_scan``."""
    secs = scope_seconds(records, "ssm_scan")
    calls = (records.get("counters") or {}).get("ssm_calls_traced")
    if not secs or not calls:
        return None
    cfg, kind = records["config"], records["device"]["kind"]
    least = 0.0
    for call in calls:  # {rows, length, chunk, heads, groups, fwd, bwd}
        for n, backward in ((call["fwd"], False), (call["bwd"], True)):
            ops, nbytes = ssm_cost.ssd_scan_cost(
                call["rows"], call["length"], call["chunk"], call["heads"],
                cfg["mamba_head_dim"], call["groups"],
                cfg["ssm_state_size"], backward)
            least += n * peaks.least_time(ops, nbytes, kind)[0]
    return 100.0 * least / secs


def latent_experts_roofline(records) -> Optional[float]:
    """Least time by the chip's peaks for the traced steps' grouped GEMMs
    over the held experts — the rows that landed here
    (``moe_local_rows_traced``, per expert layer), two matrices an expert
    at the latent width, in the passes a step makes (three forwards and a
    backward, as ``window_trace.share_experts_roofline``) — over the
    device time of scope ``moe_experts``."""
    secs = scope_seconds(records, "moe_experts")
    c = records.get("counters") or {}
    if not secs or not c.get("moe_local_rows_traced"):
        return None
    cfg, kind = records["config"], records["device"]["kind"]
    layers = ssm_cost.layer_counts(cfg)["E"]
    rows = c["moe_local_rows_traced"] * layers
    calls = c["moe_mbs_traced"] * layers
    d = cfg.get("moe_latent_size") or cfg["hidden_size"]
    least = 0.0
    for passes, backward in ((3, False), (1, True)):
        ops, nbytes = ssm_cost.latent_ffn_cost(
            passes * rows, passes * calls, cfg["n_routed_experts"], d,
            cfg["moe_intermediate_size"], backward)
        least += peaks.least_time(ops, nbytes, kind)[0]
    return 100.0 * least / secs


def latent_local_rows_pct(records) -> Optional[float]:
    """(token, expert) pairs that chose an expert held on this chip over
    all pairs routed, over the window's steps."""
    c = records.get("counters") or {}
    if not c.get("moe_routed_rows") or c.get("moe_local_rows") is None:
        return None
    return 100.0 * c["moe_local_rows"] / c["moe_routed_rows"]
