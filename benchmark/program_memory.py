"""What the cell's programs need of the chip beside what is resident,
read from the program's compile ledger
(``areal_tpu/base/compile_watch.py``, ``CacheStats``) as it stood when
warm-up ended: the drivers copy ``compile_watch.cache_stats()`` into
``records["setup_split"]["compile_cache_after_warmup"]``. Shared by the
readers of the programs' heaps under ``metrics/``. No jax.

The ledger keeps one record per executable in
``programs[fn]["executables"]``: ``label`` (what the engine said of the
program before it dispatched it: ``grid``, for a grad program ``remat``
and ``reckoned_heap_bytes``, the engine's own figure for its temporaries)
and the compiler's statistics of that executable, ``temp_bytes`` (the
program's heap, ONE chip's) among them. ``memory_stats()`` — what
``train_hbm_peak_gb`` reads — sees resident buffers only; the heaps are
these. A program without the records (the ledger before they were added,
or a backend that gives no statistics) gives None everywhere, and the
line leaves the metrics out.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from benchmark import setup_ledger

GB = 1e9
GRAD_PROGRAM = "train_grad_sliced"
INFER_PROGRAM = "infer_forward"


def executables(records: Dict[str, Any], fn: Optional[str] = None,
                ) -> List[Dict[str, Any]]:
    """The records of program ``fn``'s executables (of every program's
    without one) whose statistics the ledger found."""
    led = setup_ledger.ledger(records)
    if led is None:
        return []
    rows = led["programs"].values() if fn is None else [
        led["programs"].get(fn) or {}]
    return [rec for row in rows for rec in row.get("executables") or []
            if rec.get("temp_bytes") is not None]


def heap_gb(records: Dict[str, Any], fn: Optional[str] = None,
            ) -> Optional[float]:
    """The largest heap among those executables, in GB."""
    found = executables(records, fn)
    if not found:
        return None
    return max(rec["temp_bytes"] for rec in found) / GB


def heap_misreckoned_pct(records: Dict[str, Any]) -> Optional[float]:
    """How far the engine's reckoning of a packed grid's grad program is
    from the compiler's, at the grid where it is furthest: |reckoned −
    compiled| / compiled in per cent, compiled being the heap of the
    grid's grad program that needs most (the one with a carry and the one
    without are two executables under one reckoning, which has to hold
    for both). None with ``remat`` off (no reckoning)."""
    grids: Dict[Any, List[int]] = {}
    for rec in executables(records, GRAD_PROGRAM):
        label = rec["label"]
        if label.get("reckoned_heap_bytes") is not None:
            key = (label.get("grid"), label.get("remat"),
                   label["reckoned_heap_bytes"])
            grids.setdefault(key, []).append(rec["temp_bytes"])
    off = [abs(reckoned - max(temps)) / max(temps) * 100.0
           for (_, _, reckoned), temps in grids.items() if max(temps) > 0]
    return max(off) if off else None
