"""The traced run seen through Trinity-Mini's family (``afmoe``) — what the
per-layer metrics ``afmoe_*`` read. The windowed kernel, the share's
routing counters and the scopes inside ``moe`` are read as the Mellum and
Nemotron cells read theirs (``window_trace``, ``moe_trace``,
``ssm_trace.scope_busy_pct``); what is this family's own is here: device
self time under the scopes of gated attention and the sandwich norms
(``attn_gate``, ``post_attn_norm``, ``post_mlp_norm``:
``areal_tpu/base/telemetry.SANDWICH_SCOPES``) and of the leading dense
block's FFN (``mlp``, its post-norm apart), read from the same trace file
the same way as ``ssm_trace`` reads its scopes; the grouped GEMMs' least
time over the EXPERT blocks (the dense block has none); and the share's
parameter count. A program without these scopes or counters (the parent
commit) gives None and the metric leaves the line. No jax.
"""

from __future__ import annotations

import bisect
from typing import Any, Dict, Optional

from benchmark import moe_cost, moe_trace, peaks
from benchmark import program_trace as pt
from benchmark.trace_reduce import DEVICE_PLANE, OPS_LINE, _union

SANDWICH_SCOPES = ("attn_gate", "post_attn_norm", "post_mlp_norm")
SCOPES = SANDWICH_SCOPES + ("mlp",)


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


def expert_layers(cfg: Dict[str, Any]) -> int:
    return cfg["num_hidden_layers"] - int(cfg.get("num_dense_layers") or 0)


# ---- what the metric files under metrics/ call ----

def scope_busy_pct(records, *scopes: str) -> Optional[float]:
    """Self time under ``scopes`` over device busy time; None where the
    trace holds none of the family's scopes (a program without them)."""
    red = load(records)
    if not red or not any(s in red["scopes"] for s in SANDWICH_SCOPES):
        return None
    return 100.0 * sum(red["scopes"].get(s, 0.0)
                       for s in scopes) / red["busy_s"]


def experts_roofline(records) -> Optional[float]:
    """Least time by the chip's peaks for the traced steps' grouped GEMMs
    over the held experts — the rows that landed here
    (``moe_local_rows_traced``, per expert layer) through experts of K
    ``hidden_size`` / N ``moe_intermediate_size``, ``num_experts`` groups
    a call, on the EXPERT blocks, in the passes a step makes (three
    forwards and a backward, as ``window_trace.share_experts_roofline``)
    — over the device time of the scope ``moe_experts``."""
    red = moe_trace.load(records)
    c = records.get("counters") or {}
    if (not red or red["scopes"] is None
            or not red["scopes"].get("moe_experts")
            or not c.get("moe_local_rows_traced")):
        return None
    cfg, kind = records["config"], records["device"]["kind"]
    layers = expert_layers(cfg)
    rows = c["moe_local_rows_traced"] * layers
    calls = c["moe_mbs_traced"] * layers
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    least = 0.0
    for passes, backward in ((3, False), (1, True)):
        ops, nbytes = moe_cost.grouped_ffn_cost(
            passes * rows, passes * calls, cfg["num_experts"], d, f, backward)
        least += peaks.least_time(ops, nbytes, kind)[0]
    return 100.0 * least / red["scopes"]["moe_experts"]


def share_params(cfg: Dict[str, Any]) -> int:
    """Parameters one token multiplies through ON THIS SHARE in a forward
    pass — the N of 6·N·T for the cell's utilisation: the five attention
    projections (the gate among them) of every block, the dense blocks'
    FFN, and on each expert block the router, the shared expert and the
    held part of a token's ``num_experts_per_tok`` experts (held / routed
    of them on average), and the sliced head. The embedding is a lookup
    and the norms multiply elementwise: neither is counted."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    nq, nkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    routed = cfg.get("num_routed_experts") or cfg["num_experts"]
    fe = cfg["moe_intermediate_size"]
    attn = 2 * d * nq * dh + 2 * d * nkv * dh + nq * dh * d
    dense = int(cfg.get("num_dense_layers") or 0)
    moe = (d * routed + 3 * d * fe * (cfg.get("num_shared_experts") or 0)
           + cfg["num_experts_per_tok"] * cfg["num_experts"] / routed
           * 3 * d * fe)
    return int(cfg["num_hidden_layers"] * attn
               + dense * 3 * d * cfg["intermediate_size"]
               + expert_layers(cfg) * moe + d * v)
