"""The traced run seen through the sliding-window layers and the share of
the expert layer — what the per-layer metrics ``window_*`` and ``share_*``
read: the windowed kernel's device ops by name, its operations and bytes
(kept with the benchmark, as ``peaks.flash_attention_cost`` is), the
trace-time count of key blocks the driver put into the records, and the
share's routing counters. ``share_*`` read the same trace the same way as
``moe_*`` do (``moe_trace``), with the rows that landed on this chip. A
program without the kernel or the counters gives None and the metric
leaves the line. No jax.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Optional, Tuple

from benchmark import moe_cost, moe_trace, peaks

# The windowed kernel on the device's op line: jax's splash-attention
# kernels keep their own names (``splash_mqa_fwd_segmented_residuals``,
# ``..._dkv_...``, ``..._dq_...``), which the flash readers' patterns
# (``readers.FLASH_FWD`` / ``FLASH_BWD``) do not match.
WINDOW_OP = re.compile(r"splash_\w+?_(fwd|dkv|dq)")


def window_attention_cost(rows: int, length: int, window: int, tile: int,
                          n_q_heads: int, n_kv_heads: int, head_dim: int,
                          backward: bool, bytes_per_el: int = 2,
                          ) -> Tuple[float, float]:
    """(operations, bytes) the ALGORITHM needs for one sliding-window
    attention call over a packed [rows, length] grid, at the published
    head sizes: per block of ``tile`` queries the keys of ``min(position,
    window)`` plus one tile (the block's own keys), so a query block that
    starts at position s multiplies against ``min(s, window) + tile``
    keys; QK^T and PV are 2 matmuls x 2 flops a (query, key) pair a head
    of ``head_dim``. Q is read and O written once, K and V are read once
    at the ``n_kv_heads`` they have (not repeated, not lane-padded). The
    backward pass needs 2.5x the forward's matmul work (dQ, dK, dV and the
    recomputed scores) and reads Q, K, V, O, dO and writes dQ, dK, dV.
    Several documents in a row only remove work, as in
    ``peaks.flash_attention_cost``."""
    pairs = sum(tile * (min(s, window) + tile)
                for s in range(0, length, tile))
    fwd_ops = 2 * 2 * rows * n_q_heads * pairs * head_dim
    q_el = rows * length * n_q_heads * head_dim
    kv_el = rows * length * n_kv_heads * head_dim
    if not backward:
        return fwd_ops, bytes_per_el * (2 * q_el + 2 * kv_el)
    return 2.5 * fwd_ops, bytes_per_el * (4 * q_el + 4 * kv_el + q_el)


def window_times(records: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """Self seconds of the windowed kernels in the traced window, by pass:
    {"fwd": s, "dkv": s, "dq": s}; None on a trace without them."""
    ops = (records.get("trace") or {}).get("ops") or {}
    out: Dict[str, float] = {}
    for name, secs in ops.items():
        m = WINDOW_OP.search(name)
        if m:
            out[m.group(1)] = out.get(m.group(1), 0.0) + secs
    return out or None


def window_attn_busy_pct(records) -> Optional[float]:
    wt = window_times(records)
    if wt is None:
        return None
    return 100.0 * sum(wt.values()) / records["trace"]["busy_s"]


def window_geometry(records) -> Optional[Dict[str, Dict[str, Any]]]:
    """{"length>padded/tile/wW": {calls, blocks_visited, blocks_causal,
    rows}} of the train step's windowed calls, as the driver copied them
    out of the program's trace-time count; None where the program has no
    such count."""
    return (records.get("counters") or {}).get("window_geometry") or None


def window_blocks_visited_pct(records) -> Optional[float]:
    geo = window_geometry(records)
    if not geo:
        return None
    visited = sum(g["blocks_visited"] for g in geo.values())
    causal = sum(g["blocks_causal"] for g in geo.values())
    return 100.0 * visited / causal if causal else None


def window_attn_roofline(records) -> Optional[float]:
    """Least time the chip's peaks allow for the traced steps' windowed
    calls over the kernels' time. The calls of a step are what its grad
    and inference programs traced (``window_geometry``: per packed grid,
    one call a sliding layer a pass), run once a micro-batch of that grid:
    an inference forward, the train forward, and a backward — the
    recomputing forward is left out where the trace-time count of the
    grad program says the kernel's output was kept."""
    wt, c = window_times(records), records.get("counters") or {}
    calls = c.get("window_calls_traced")
    if wt is None or not calls:
        return None
    cfg, kind = records["config"], records["device"]["kind"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    least = 0.0
    for call in calls:  # {rows, length, window, tile, fwd, bwd}
        for n, backward in ((call["fwd"], False), (call["bwd"], True)):
            ops, nbytes = window_attention_cost(
                call["rows"], call["length"], call["window"], call["tile"],
                nq, nkv, cfg["head_dim"], backward)
            least += n * peaks.least_time(ops, nbytes, kind)[0]
    return 100.0 * least / sum(wt.values())


# ---- the share of the expert layer ----

def share_local_rows_pct(records) -> Optional[float]:
    """(token, expert) pairs that chose an expert held on this chip over
    all pairs routed, over the window's steps."""
    c = records.get("counters") or {}
    if not c.get("moe_routed_rows") or c.get("moe_local_rows") is None:
        return None
    return 100.0 * c["moe_local_rows"] / c["moe_routed_rows"]


def share_experts_roofline(records) -> Optional[float]:
    """Least time by the chip's peaks for the traced steps' grouped GEMMs
    over the held experts — the rows that landed here (``moe_local_rows``
    of each traced step, per layer), in the passes a step makes (as
    ``moe_trace.experts_roofline``: three forwards and a backward) — over
    the device time of the scope ``moe_experts``."""
    red = moe_trace.load(records)
    c = records.get("counters") or {}
    if (not red or red["scopes"] is None
            or not red["scopes"].get("moe_experts")
            or not c.get("moe_local_rows_traced")):
        return None
    cfg, kind = records["config"], records["device"]["kind"]
    layers = cfg["num_hidden_layers"]
    rows = c["moe_local_rows_traced"] * layers
    calls = c["moe_mbs_traced"] * layers
    d, f = cfg["hidden_size"], moe_cost.expert_width(cfg)
    least = 0.0
    for passes, backward in ((3, False), (1, True)):
        ops, nbytes = moe_cost.grouped_ffn_cost(
            passes * rows, passes * calls, cfg["num_experts"], d, f, backward)
        least += peaks.least_time(ops, nbytes, kind)[0]
    return 100.0 * least / red["scopes"]["moe_experts"]


def share_params(cfg: Dict[str, Any]) -> int:
    """Parameters one token multiplies through ON THIS SHARE in a forward
    pass — the N of 6·N·T for the cell's utilisation: the attention
    projections and the router of every layer, the held part of a token's
    ``num_experts_per_tok`` experts (held / routed of them on average),
    and the sliced head."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    nq, nkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    routed = cfg.get("num_routed_experts") or cfg["num_experts"]
    attn = d * nq * dh + 2 * d * nkv * dh + nq * dh * d
    moe = (d * routed + cfg["num_experts_per_tok"] * cfg["num_experts"]
           / routed * 3 * d * moe_cost.expert_width(cfg))
    return int(cfg["num_hidden_layers"] * (attn + moe) + d * v)
