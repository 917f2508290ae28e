"""Arithmetic shared by the per-layer metric readers under ``metrics/``.
A reader takes the run's ``records`` (what the driver wrote: counters,
host timings, the reduced trace) and returns its value, or None where the
records hold nothing to read. No jax.
"""

from __future__ import annotations

import re
import statistics
from typing import Any, Dict, List, Optional

from benchmark import peaks

# How the Pallas flash-attention kernels appear on the device's op line
# (the kernels' own names, ops/pallas/flash_attention.py): the forward
# kernel, and the backward's two kernels, of which dkv runs once a pass.
FLASH_FWD = re.compile(r"^flash_attention(\.\d+)? ")
FLASH_BWD = re.compile(r"^flash_mha_bwd_(dkv|dq)")
FLASH_BWD_PASS = re.compile(r"^flash_mha_bwd_dkv")
GRID = re.compile(r"\[(\d+),(\d+),(\d+),(\d+)\]")  # [rows, heads, L, Dh]


def device_idle_pct(records: Dict[str, Any]) -> Optional[float]:
    """1 - busy/window on the WORST chip of the traced window."""
    tr = records.get("trace") or {}
    if not tr.get("busy_s_per_chip"):
        return None
    return 100.0 * (1.0 - min(tr["busy_s_per_chip"]) / tr["window_s"])


def hbm_peak_gb(records: Dict[str, Any]) -> Optional[float]:
    b = records.get("memory_peak_bytes")
    return None if b is None else b / 1e9


def flash_times(records: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Self seconds of the flash kernels in the traced window, and per
    pass (kind, rows, length) how often it ran."""
    tr = records.get("trace") or {}
    ops, calls = tr.get("ops") or {}, tr.get("op_calls") or {}
    out: Dict[str, Any] = {"fwd_s": 0.0, "bwd_s": 0.0, "passes": {}}
    for name, secs in ops.items():
        kind = ("bwd" if FLASH_BWD.search(name)
                else "fwd" if FLASH_FWD.search(name) else None)
        if kind is None:
            continue
        out[kind + "_s"] += secs
        g = GRID.search(name)
        if g and (kind == "fwd" or FLASH_BWD_PASS.search(name)):
            key = (kind, int(g.group(1)), int(g.group(3)))
            out["passes"][key] = out["passes"].get(key, 0) + calls.get(name, 0)
    return out if out["passes"] else None


def flash_attn_busy_pct(records: Dict[str, Any]) -> Optional[float]:
    ft = flash_times(records)
    if ft is None:
        return None
    return 100.0 * (ft["fwd_s"] + ft["bwd_s"]) / records["trace"]["busy_s"]


def flash_attn_roofline(records: Dict[str, Any]) -> Optional[float]:
    """Least time the chip's peaks allow for the traced passes over the
    kernels' time. Each pass's [rows, length] grid is read off the op's
    own output shape; heads and head size are the configuration's
    published ones, not the padded ones the kernel was handed."""
    ft = flash_times(records)
    if ft is None:
        return None
    cfg, kind = records["config"], records["device"]["kind"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg.get("head_dim") or cfg["hidden_size"] // nq
    least = 0.0
    for (k, rows, length), n in ft["passes"].items():
        ops, nbytes = peaks.flash_attention_cost(
            rows, length, nq, nkv, dh, backward=(k == "bwd"))
        least += n * peaks.least_time(ops, nbytes, kind)[0]
    return 100.0 * least / (ft["fwd_s"] + ft["bwd_s"])


def window_throughput(steps: List[Dict[str, Any]], batch_tokens: List[int],
                      ) -> Dict[str, Any]:
    """The train window's reduction. ``steps`` are the window's steps in
    order, each ``{"batch", "secs", "traced"}``; the window visits the
    batches in turn, so every batch is visited several times.

    ``tok_s``        the batches' tokens over the sum of every batch's mean
                     step time with its ONE slowest visit left out (the
                     end-to-end metric: a stall that comes once in a
                     window does not set the number, anything that recurs
                     counts in proportion);
    ``mean_tok_s``   the same with every visit counted: every step of the
                     window, nothing taken out, and a window that ends
                     between two batches does not weigh them unevenly;
    ``slow_step_s``  seconds spent above each batch's median step time.

    Steps that the profiler's start or stop fell into are left out of all
    three (a traced run reports no end-to-end metric)."""
    by_batch: Dict[int, List[float]] = {}
    for s in steps:
        if not s["traced"]:
            by_batch.setdefault(s["batch"], []).append(s["secs"])
    if len(by_batch) < len(batch_tokens):
        return {"tok_s": None, "mean_tok_s": None, "slow_step_s": None}
    kept = [sorted(v)[:-1] if len(v) > 1 else v for v in by_batch.values()]
    return {
        "tok_s": sum(batch_tokens) / sum(statistics.fmean(v) for v in kept),
        "mean_tok_s": sum(batch_tokens) / sum(
            statistics.fmean(v) for v in by_batch.values()),
        "slow_step_s": sum(max(0.0, x - statistics.median(v))
                           for v in by_batch.values() for x in v),
    }
