"""What set-up cost, read from the program's compile ledger
(``areal_tpu/base/compile_watch.py``, ``CacheStats``) as it stood when
warm-up ended: the drivers copy ``compile_watch.cache_stats()`` into
``records["setup_split"]["compile_cache_after_warmup"]``. Shared by the
``setup_*`` readers under ``metrics/``. No jax.

Only the ledger's cumulative fields are read (the totals, ``busy_secs``,
``programs[fn]``), never its ring of spans, so a span the ring dropped
loses no second. A program without the ledger (its ``cache_stats()`` has
no ``programs``) gives None everywhere, and the line leaves the metrics
out.

The three parts are disjoint and add up to the stopwatch's set-up
(``stopwatch_s``: the three blocks the drivers time from outside):

``compile_s``        seconds inside a backend compile call (a real compile
                     on a cache miss, a cache read on a hit);
``trace_lower_s``    seconds inside a trace or a lower span and not inside
                     a compile: the part no cache saves;
``unattributed_s``   the stopwatch less both: imports, weights, uploads,
                     first executions — what no ledger span covers.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

STOPWATCH_BLOCKS = ("imports_s", "weights_backend_s", "warmup_s")


def ledger(records: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    led = (records.get("setup_split") or {}).get("compile_cache_after_warmup")
    return led if led and "programs" in led else None


def stopwatch_s(records: Dict[str, Any]) -> Optional[float]:
    split = records.get("setup_split") or {}
    if any(k not in split for k in STOPWATCH_BLOCKS):
        return None
    return sum(split[k] for k in STOPWATCH_BLOCKS)


def compile_s(records: Dict[str, Any]) -> Optional[float]:
    led = ledger(records)
    return None if led is None else led["compile_secs"]


def trace_lower_s(records: Dict[str, Any]) -> Optional[float]:
    """The union of ALL spans less the compile spans' union: a small
    program compiled inside a trace counts as compile, not twice."""
    led = ledger(records)
    return None if led is None else led["busy_secs"] - led["compile_secs"]


def unattributed_s(records: Dict[str, Any]) -> Optional[float]:
    led, total = ledger(records), stopwatch_s(records)
    if led is None or total is None:
        return None
    return total - led["busy_secs"]


def cache_misses(records: Dict[str, Any]) -> Optional[int]:
    led = ledger(records)
    return None if led is None else led["misses"]


def programs(records: Dict[str, Any]) -> Optional[int]:
    """Backend compile calls in set-up: executables made or read back."""
    led = ledger(records)
    if led is None:
        return None
    return sum(row["n_compile"] for row in led["programs"].values())


def slowest_program_s(records: Dict[str, Any]) -> Optional[float]:
    led = ledger(records)
    if led is None or not led["programs"]:
        return None
    return max(row["max_secs"] for row in led["programs"].values())
