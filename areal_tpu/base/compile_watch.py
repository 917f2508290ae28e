"""Compile-event observatory: jit entry-point tracing, recompile-storm
detection, and persistent-cache accounting.

The observability stack can say where wall-clock goes (telemetry spans,
goodput states) but was blind to the failure mode that actually dominates
TPU-native JAX operation: XLA compilation. BENCH_r08 died inside a warmup
compile no metric could see, and the sentinel papered over the hole with a
blanket 30-minute ``trainer_stalled`` grace. This module makes compilation
a first-class, alertable signal:

 - :func:`watched_jit` / :meth:`CompileWatch.wrap` shim an ALREADY-JITTED
   callable. Each call's abstract signature (shape/dtype of array leaves,
   values of static args) is computed host-side; a signature this wrapper
   has not seen is exactly the condition under which ``jax.jit`` traces
   and compiles, so the wall time of that first call is recorded as a
   compile event (first-execution-inclusive — XLA holds the caller through
   compile + the initial dispatch). Signature sets are PER WRAPPER, not
   per name: a fresh ``jax.jit`` object (new grad-fn cache entry, a
   reshard identity built per group) recompiles even for a shape some
   other wrapper saw, and the ledger must say so.
 - Per-function families on the PR-4 telemetry registry:
   ``compile/events{fn=...}`` / ``compile/secs{fn=...}`` counters, a
   ``compile/inflight`` gauge (nonzero while any wrapped call is tracing)
   and ``compile/distinct_shapes{fn=...}`` — the same family the serving
   ShapeBucketPolicy feeds, so trainer ``[R, L]`` packed grids and decode
   bucket shapes are audited with one ruler.
 - A recompile-storm detector: a NEW signature for a function that had
   been shape-stable for ``storm_warmup_calls`` calls increments
   ``compile/storm_events`` and logs the offending signature once — the
   signal the sentinel's ``recompile_storm`` rate rule watches.
 - Persistent-cache accounting: the entry count of the persistent
   compilation cache (:func:`compilation_cache_dir`) is probed around
   each observed compile — an entry appearing means XLA really compiled
   (``compile/cache_misses``); none appearing means the compile was
   served from the persistent cache (``compile/cache_hits``).

Disabled contract (mirrors telemetry/goodput): until :func:`configure`
installs an enabled watch, :func:`watched_jit` returns the raw function
object unchanged — zero wrappers, zero per-call work, and the Prometheus
scrape is bit-identical to a build without this module.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Callable, Dict, Optional, Set

from areal_tpu.base import logging, telemetry

logger = logging.getLogger("base.compile_watch")

# Where JAX_COMPILATION_CACHE_DIR is unset the cache lives at ONE fixed
# path inside the checkout: the directory is part of the cache key, so a
# path built from a temp name, a pid or the time would never hit.
DEFAULT_COMPILATION_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    ))),
    ".jax_cache",
)


def compilation_cache_dir() -> str:
    """The persistent compilation cache every compiling process shares:
    the standard ``JAX_COMPILATION_CACHE_DIR`` when set, else the fixed
    in-checkout default. The observatory's hit/miss probe watches the
    same directory :func:`enable_compilation_cache` arms."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or DEFAULT_COMPILATION_CACHE)


class CacheStats:
    """This process's persistent-cache traffic, counted from jax's own
    monitoring events: ``hits`` (executables read back), ``misses``
    (compiled and written), and the seconds a program costs before it
    runs: ``trace_secs`` (Python → jaxpr), ``lower_secs`` (jaxpr → MLIR
    module), ``compile_secs`` (wall time inside the backend compile call —
    a cache read when it hits) and ``cache_read_secs`` (the part of that
    spent reading the cache)."""

    _DURATIONS = {
        "/jax/core/compile/jaxpr_trace_duration": "trace_secs",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_secs",
        "/jax/core/compile/backend_compile_duration": "compile_secs",
        "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read_secs",
    }

    def __init__(self) -> None:
        self.hits = 0
        self.misses = 0
        self.secs = dict.fromkeys(self._DURATIONS.values(), 0.0)

    def _on_event(self, event: str, **_: Any) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on_duration(self, event: str, secs: float, **_: Any) -> None:
        field = self._DURATIONS.get(event)
        if field is not None:
            self.secs[field] += secs

    def as_dict(self) -> Dict[str, Any]:
        return {"dir": compilation_cache_dir(), "hits": self.hits,
                "misses": self.misses,
                **{k: round(v, 3) for k, v in self.secs.items()}}


_CACHE_STATS: Optional[CacheStats] = None


def cache_stats() -> Optional[Dict[str, Any]]:
    """Cache traffic of this process so far; None where
    :func:`enable_compilation_cache` never ran."""
    return _CACHE_STATS.as_dict() if _CACHE_STATS is not None else None


def enable_compilation_cache() -> None:
    """Arm JAX's persistent compilation cache for this process (launcher
    children, the benchmark's drivers and chip_smoke.py's phases all call
    this one helper) and start counting its hits and misses. jax reads
    ``JAX_COMPILATION_CACHE_DIR`` itself, so a directory is set in code
    only when the variable is not. Imports jax and sets config — never
    creates an array or asks for devices."""
    global _CACHE_STATS
    import jax

    if _CACHE_STATS is not None:
        return
    path = compilation_cache_dir()
    os.makedirs(path, exist_ok=True)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    # Cache everything (the default skips compiles under 1 s): the fleet
    # spawns several processes that compile the same small graphs.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    _CACHE_STATS = CacheStats()
    jax.monitoring.register_event_listener(_CACHE_STATS._on_event)
    jax.monitoring.register_event_duration_secs_listener(
        _CACHE_STATS._on_duration
    )


def abstract_signature(args: tuple, kwargs: dict) -> str:
    """The host-side stand-in for jax.jit's cache key: array-like leaves
    (anything with ``.shape`` and ``.dtype``) collapse to ``dtype[shape]``,
    containers recurse, and everything else — the static args whose VALUES
    key the jit cache (``S``, ``n_tokens``, config objects) — contributes
    a bounded repr. Pure string math, no jax import: jax-free tests feed
    lightweight fakes through the same path the fleet runs."""
    parts: list = []

    def walk(x: Any) -> None:
        if isinstance(x, (list, tuple)):
            parts.append("(" if isinstance(x, tuple) else "[")
            for v in x:
                walk(v)
            parts.append(")" if isinstance(x, tuple) else "]")
        elif isinstance(x, dict):
            parts.append("{")
            for k in sorted(x, key=str):
                parts.append(f"{k}:")
                walk(x[k])
            parts.append("}")
        else:
            shape = getattr(x, "shape", None)
            dtype = getattr(x, "dtype", None)
            if shape is not None and dtype is not None:
                try:
                    dims = ",".join(str(int(d)) for d in shape)
                except (TypeError, ValueError):
                    dims = str(shape)
                parts.append(f"{dtype}[{dims}]")
            elif x is None or isinstance(x, (bool, int, float, str, bytes)):
                parts.append(repr(x))
            else:
                # Hashable static arg (model config, mesh): identity by a
                # bounded repr — enough to tell bucket ladders apart
                # without serializing a whole config tree per call.
                parts.append(f"{type(x).__name__}:{repr(x)[:160]}")

    walk(args)
    parts.append("|")
    walk(kwargs)
    return "".join(parts)


class _FnRecord:
    """Per-NAME aggregate: the union of signatures any wrapper observed
    (the distinct-shapes gauge) and the shape-stability counter the storm
    detector runs on."""

    __slots__ = ("signatures", "calls", "calls_since_new_sig")

    def __init__(self) -> None:
        self.signatures: Set[str] = set()
        self.calls = 0
        self.calls_since_new_sig = 0


class _WatchedFn:
    """The wrapper :meth:`CompileWatch.wrap` returns. Owns its own
    seen-signature set (fresh jit objects recompile known shapes); the
    shared watch owns the per-name aggregates and metric export."""

    __slots__ = ("_watch", "_name", "_fn", "_seen")

    def __init__(self, watch: "CompileWatch", name: str, fn: Callable):
        self._watch = watch
        self._name = name
        self._fn = fn
        self._seen: Set[str] = set()

    @property
    def __wrapped__(self) -> Callable:
        return self._fn

    def __call__(self, *args, **kwargs):
        sig = abstract_signature(args, kwargs)
        if sig in self._seen:
            self._watch._note_call(self._name)
            return self._fn(*args, **kwargs)
        self._seen.add(sig)
        self._watch._compile_begin()
        t0 = self._watch._clock()
        try:
            return self._fn(*args, **kwargs)
        finally:
            self._watch._compile_end(
                self._name, sig, self._watch._clock() - t0
            )


class CompileWatch:
    """Process-wide (or per-server) compile-event registry.

    ``telemetry_sink`` is any Telemetry-like object (``inc`` /
    ``set_gauge`` / ``event``); ``clock`` is injectable for fake-clock
    tests. ``cache_dir=None`` disables persistent-cache accounting."""

    enabled = True

    def __init__(self, telemetry_sink=None, *,
                 storm_warmup_calls: int = 16,
                 cache_dir: Optional[str] = None,
                 clock: Callable[[], float] = time.monotonic):
        self.tel = telemetry_sink if telemetry_sink is not None \
            else telemetry.get()
        self.storm_warmup_calls = max(int(storm_warmup_calls), 1)
        self.cache_dir = cache_dir
        self._clock = clock
        self._lock = threading.Lock()
        self._fns: Dict[str, _FnRecord] = {}
        self._inflight = 0
        self._warned_storms: Set[str] = set()
        self._cache_entries = self._count_cache_entries()

    # ---- wrapping ----

    def wrap(self, name: str, fn: Callable) -> Callable:
        return _WatchedFn(self, name, fn)

    def inflight(self) -> bool:
        """True while any wrapped call is inside its first-signature
        (trace + compile) execution — the HeartbeatThread publishes this
        so sentinel absence rules can tell "wedged" from "compiling"."""
        return self._inflight > 0

    # ---- internals (called by _WatchedFn) ----

    def _note_call(self, name: str) -> None:
        with self._lock:
            rec = self._fns.get(name)
            if rec is None:
                rec = self._fns[name] = _FnRecord()
            rec.calls += 1
            rec.calls_since_new_sig += 1

    def _compile_begin(self) -> None:
        with self._lock:
            self._inflight += 1
            self.tel.set_gauge("compile/inflight", float(self._inflight))

    def _compile_end(self, name: str, sig: str, secs: float) -> None:
        storm = False
        with self._lock:
            self._inflight -= 1
            self.tel.set_gauge("compile/inflight", float(self._inflight))
            rec = self._fns.get(name)
            if rec is None:
                rec = self._fns[name] = _FnRecord()
            rec.calls += 1
            if sig not in rec.signatures:
                # A new shape after the fn had been stable through the
                # warmup window is the storm signature: something churns
                # past the bucket policy (length distribution drift, a
                # mis-rounded batch dim) and every occurrence costs a
                # full XLA compile on the hot path.
                storm = (rec.calls_since_new_sig >= self.storm_warmup_calls
                         and bool(rec.signatures))
                rec.signatures.add(sig)
                rec.calls_since_new_sig = 0
            n_shapes = len(rec.signatures)
        self.tel.inc(f"compile/events{{fn={name}}}")
        self.tel.inc(f"compile/secs{{fn={name}}}", max(secs, 0.0))
        self.tel.set_gauge(f"compile/distinct_shapes{{fn={name}}}",
                           float(n_shapes))
        if storm:
            self.tel.inc("compile/storm_events")
            key = f"{name}|{sig}"
            if key not in self._warned_storms:
                self._warned_storms.add(key)
                logger.warning(
                    f"recompile storm: {name} compiled a NEW shape after "
                    f"being stable for >= {self.storm_warmup_calls} calls "
                    f"— offending signature: {sig[:512]}"
                )
            self.tel.event("compile/storm", fn=name, sig=sig[:512])
        self._probe_cache()

    # ---- persistent-cache accounting ----

    def _count_cache_entries(self) -> Optional[int]:
        if not self.cache_dir:
            return None
        try:
            return len(os.listdir(self.cache_dir))
        except OSError:
            return None

    def _probe_cache(self) -> None:
        """Around each observed compile: a new entry in the persistent
        cache dir means XLA really compiled (miss — it wrote the result);
        no new entry means the compile was served from cache (hit)."""
        if self.cache_dir is None:
            return
        count = self._count_cache_entries()
        if count is None:
            return
        prev, self._cache_entries = self._cache_entries, count
        if prev is not None and count > prev:
            self.tel.inc("compile/cache_misses", float(count - prev))
        else:
            self.tel.inc("compile/cache_hits")

    # ---- views ----

    def stats(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            return {
                name: {
                    "calls": float(rec.calls),
                    "distinct_shapes": float(len(rec.signatures)),
                }
                for name, rec in self._fns.items()
            }

    def close(self) -> None:
        pass


class _NullCompileWatch:
    """Shared disabled sink: wrap() hands the raw fn back — the call path
    is bit-identical to a build without this module."""

    enabled = False

    def wrap(self, name: str, fn: Callable) -> Callable:
        return fn

    def inflight(self) -> bool:
        return False

    def stats(self) -> Dict[str, Dict[str, float]]:
        return {}

    def close(self) -> None:
        pass


NULL = _NullCompileWatch()
_GLOBAL: Any = NULL


def configure(cfg=None, telemetry_sink=None,
              cache_dir: Optional[str] = "auto",
              clock: Callable[[], float] = time.monotonic):
    """Install the process-global compile watch. A disabled (or absent)
    config keeps the null sink — jit sites never re-check.

    ``cache_dir="auto"`` resolves :func:`compilation_cache_dir`; pass
    None to disable cache accounting."""
    global _GLOBAL
    if cfg is None or not getattr(cfg, "enabled", False):
        _GLOBAL = NULL
        return NULL
    if cache_dir == "auto":
        cache_dir = compilation_cache_dir()
    _GLOBAL = CompileWatch(
        telemetry_sink,
        storm_warmup_calls=getattr(cfg, "storm_warmup_calls", 16),
        cache_dir=cache_dir,
        clock=clock,
    )
    return _GLOBAL


def get():
    return _GLOBAL


def enabled() -> bool:
    return _GLOBAL.enabled


def watched_jit(name: str, fn: Callable) -> Callable:
    """Wrap an already-jitted callable under the process-global watch
    (the raw fn comes straight back while disabled). Call at jit-creation
    sites: ``fn = compile_watch.watched_jit("train/grad", jax.jit(f))``."""
    return _GLOBAL.wrap(name, fn)


def inflight() -> bool:
    return _GLOBAL.inflight()


def shutdown() -> None:
    global _GLOBAL
    if _GLOBAL is not NULL:
        _GLOBAL.close()
        _GLOBAL = NULL
