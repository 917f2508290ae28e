"""Compile-event observatory: the compile ledger (what every program cost
before it first ran), jit entry-point tracing and recompile-storm
detection.

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
   other wrapper saw, and the watch must say so.
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
 - Persistent-cache accounting: ``compile/cache_hits`` /
   ``compile/cache_misses`` are the compile ledger's counts (jax's own
   events) on the calling thread around each observed compile.

The compile ledger (:class:`CacheStats`, :func:`cache_stats`) is the other
instrument and needs no switch: :func:`enable_compilation_cache` arms it
in every compiling process, and it runs only when jax traces, lowers or
compiles. It also keeps one record per EXECUTABLE: what the engine said of
it before it dispatched it (:func:`label`: the packed grid, what the
backward re-runs, the engine's own reckoning of its heap) and what the
compiler says it needs (``get_compiled_memory_stats()`` of the executable
the ``compile`` span produced — the program heap that ``memory_stats()``
does not see). An enabled watch also gets each program's stage spans as
``compile/<stage>`` spans of its telemetry sink.

Disabled contract (mirrors telemetry/goodput): until :func:`configure`
installs an enabled watch, :func:`watched_jit` returns the raw function
object unchanged — zero wrappers, zero per-call work, and the Prometheus
scrape is bit-identical to a build without this module.
"""

from __future__ import annotations

import collections
import os
import re
import threading
import time
import weakref
from typing import Any, Callable, Deque, Dict, Optional, Set, Tuple

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
    in-checkout default."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or DEFAULT_COMPILATION_CACHE)


# jax's three stage events (dispatch.log_elapsed_time): each is sent as a
# scalar when the stage is ENTERED (its start on the wall clock) and as a
# duration and a time span when it ENDS, all with ``fun_name``.
_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
# Cache traffic that no open stage span claims (none seen so far: both of
# jax's compile paths run inside a ``compile`` span).
UNNAMED_PROGRAM = "(unnamed)"
SPAN_RING = 1024
# What the compiler says an executable needs, under the ledger's names and
# under ``CompiledMemoryStats``'. Bytes of ONE chip: an SPMD executable's
# statistics are per device. ``temp_bytes`` is the program's heap (its
# temporaries); ``peak_bytes`` the compiler's own peak of arguments,
# outputs and temporaries alive at once.
MEMORY_FIELDS = {
    "temp_bytes": "temp_size_in_bytes",
    "argument_bytes": "argument_size_in_bytes",
    "output_bytes": "output_size_in_bytes",
    "alias_bytes": "alias_size_in_bytes",
    "code_bytes": "generated_code_size_in_bytes",
    "peak_bytes": "peak_memory_in_bytes",
}


_MODULE_NAME_RE = re.compile(r"[^\w.-]")


def _module_name(fun_name: str) -> str:
    """The name jax gives the HLO module of a lower / compile span's
    ``fun_name`` (interpreters/mlir.py): ``jit_train_apply`` for
    ``jit(train_apply)``."""
    return _MODULE_NAME_RE.sub("_", fun_name).rstrip("_")


def jax_live_executables() -> list:
    """The default backend's live executables (newest first), as the
    client lists them: microseconds, no compile."""
    from jax.extend import backend

    return backend.get_backend().live_executables()


_LABEL = threading.local()
_MISSING = object()


def label(fn: str, **fields: Any) -> None:
    """What the caller knows of the program ``fn`` (the jitted function's
    name) that it is about to dispatch on this thread, and jax's name for
    it does not say: the packed grid, what its backward re-runs, what the
    engine reckons it needs. One attribute store; READ only if jax then
    compiles ``fn`` on this thread (the executable's record keeps it), and
    replaced by the next store. A compile of another program, or on
    another thread, does not see it."""
    _LABEL.value = (fn, fields)


def _label_of(fn: str) -> Dict[str, Any]:
    value = getattr(_LABEL, "value", None)
    return dict(value[1]) if value is not None and value[0] == fn else {}


def _program_name(fun_name: str) -> str:
    """``train_apply`` for both ``train_apply`` (trace) and
    ``jit(train_apply)`` (lower, compile)."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


class _OpenSpan:
    """A stage jax has entered and not left, on one thread's stack. What
    ran INSIDE it (the callees a trace traces, a small program compiled
    while lowering) is folded in as it ends: ``secs`` holds the union of
    the inner spans per stage, ``hits`` / ``misses`` / ``cache_read_secs``
    the cache traffic under it."""

    __slots__ = ("stage", "fn", "t_start", "n_children", "secs", "hits",
                 "misses", "cache_read_secs", "before")

    def __init__(self, stage: str, fn: str, t_start: float) -> None:
        self.stage = stage
        self.fn = fn
        self.t_start = t_start
        # a program's own compile span: the executables that lived when
        # it began ({id: fingerprint}); None: not known
        self.before: Optional[Dict[int, Any]] = None
        self.n_children = 0
        self.secs = {"trace_secs": 0.0, "lower_secs": 0.0,
                     "compile_secs": 0.0}
        self.hits = 0
        self.misses = 0
        self.cache_read_secs = 0.0


class CacheStats:
    """The compile ledger: what every program of this process cost before
    it first ran, from jax's own monitoring events — by name, by stage
    (``trace`` Python → jaxpr, ``lower`` jaxpr → MLIR module, ``compile``
    the backend compile call: a real compile on a cache miss, a cache read
    on a hit) and on the wall clock (``time.time()``). It runs only when
    jax traces, lowers or compiles, so it is always on.

    A span that ends while another of its thread is open is that one's
    child (``sin`` inside ``train_grad_sliced``'s trace) and is folded
    into it; a span with no parent is a PROGRAM's own, and only those are
    filed: in ``spans`` (a ring of the newest ``SPAN_RING``) and, for
    good, in ``programs[fn]`` and the process totals. Seconds are unions:
    a callee's trace inside its caller's counts once. The totals are the
    sums over ``programs``, per thread, of

    ``hits`` / ``misses``  executables read back / compiled and written;
    ``trace_secs`` ``lower_secs`` ``compile_secs``  union of that stage's
                       spans; ``cache_read_secs`` the part of
                       ``compile_secs`` spent reading the cache;
    ``busy_secs``      union of ALL stage spans (a small program compiled
                       inside a trace is in two stage unions, once here).

    ``programs[fn]`` adds ``n_trace`` / ``n_lower`` / ``n_compile`` (the
    program's own spans: ``n_compile`` is how many executables it needed),
    ``n_children`` (spans folded into them) and ``max_secs`` (the largest
    trace + lower + compile of ONE of its compilations).

    One record per EXECUTABLE, for good, in ``programs[fn]["executables"]``
    in the order compiled: ``label`` (what :func:`label` said of ``fn`` on
    the compiling thread: which grid it is), ``cache`` (``hit`` / ``miss``
    / ``uncached``), ``secs`` (that compilation's trace + lower + compile)
    and the compiler's ``MEMORY_FIELDS`` of the executable the ``compile``
    span produced — the one ``live_executables()`` lists when the span
    ends and did not when it began (where several were born meanwhile, on
    other threads, the one of its module name); one the client lists
    late is found at the next :meth:`as_dict`. Until then, and on a
    backend that gives no statistics, the fields are None
    (``executables_unmatched`` counts those still looked for). The row
    keeps ``max_temp_bytes`` / ``max_peak_bytes``, the totals
    ``max_temp_bytes`` and the ``max_temp_program`` that holds it;
    ``executables_read_secs`` is what the looking and reading cost. The
    ring's entry of that ``compile`` span carries the same fields.

    ``live_executables``: the backend's list (:func:`jax_live_executables`
    for the process's ledger); None keeps the byte fields None."""

    def __init__(self, live_executables: Optional[Callable[[], list]] = None,
                 ) -> None:
        self.hits = 0
        self.misses = 0
        self.secs = {"trace_secs": 0.0, "lower_secs": 0.0,
                     "compile_secs": 0.0, "cache_read_secs": 0.0}
        self.busy_secs = 0.0
        self.programs: Dict[str, Dict[str, Any]] = {}
        self.spans: Deque[Dict[str, Any]] = collections.deque(
            maxlen=SPAN_RING)
        self._lock = threading.Lock()
        self._local = threading.local()
        self._live = live_executables
        self._exe_lock = threading.Lock()
        # {id(executable): fingerprint} of those a record has claimed, and
        # {id: (fingerprint, module name)} of unclaimed ones whose name was
        # read (``hlo_modules()`` parses the program: 70 ms for a grad
        # program on a TPU, so only where the birth alone does not decide).
        # No reference is kept: an executable dies with its jit, and a new
        # one at its address has another fingerprint.
        self._claimed: Dict[int, Any] = {}
        self._names: Dict[int, Tuple[Any, str]] = {}
        # (module name, fn, record, ring entry, executables that lived
        # before its compile span) compiled and not found yet
        self._pending: list = []
        self.max_temp_bytes: Optional[int] = None
        self.max_temp_program: Optional[Dict[str, Any]] = None
        self.executables_read_secs = 0.0

    def _thread(self):
        """This thread's open spans, the running seconds of each program's
        compilation in progress, and its own cache counts."""
        t = self._local
        if not hasattr(t, "open"):
            t.open, t.compiling, t.hits, t.misses = [], {}, 0, 0
        return t

    def thread_counts(self) -> Tuple[int, int]:
        """(hits, misses) of the calling thread so far: a jit call
        compiles on the thread that makes it."""
        t = self._thread()
        return t.hits, t.misses

    # ---- jax.monitoring listeners ----

    def _on_enter(self, event: str, value: float, fun_name: str = "",
                  **_: Any) -> None:
        stage = _STAGES.get(event)
        if stage is not None:
            t = self._thread()
            span = _OpenSpan(stage, fun_name, value)
            if stage == "compile" and not t.open:
                span.before = self._alive()
            t.open.append(span)

    def _on_event(self, event: str, **_: Any) -> None:
        if event == _CACHE_HIT:
            self._cache_traffic(hits=1)
        elif event == _CACHE_MISS:
            self._cache_traffic(misses=1)

    def _on_duration(self, event: str, secs: float, **_: Any) -> None:
        if event == _CACHE_READ:
            self._cache_traffic(read_secs=secs)

    def _cache_traffic(self, hits: int = 0, misses: int = 0,
                       read_secs: float = 0.0) -> None:
        """The cache's events carry no name: they belong to the span open
        on their thread (the ``compile`` they fire inside)."""
        t = self._thread()
        t.hits += hits
        t.misses += misses
        with self._lock:
            self.hits += hits
            self.misses += misses
            self.secs["cache_read_secs"] += read_secs
            if not t.open:
                row = self._row(UNNAMED_PROGRAM)
                row["hits"] += hits
                row["misses"] += misses
                row["cache_read_secs"] += read_secs
                return
        span = t.open[-1]
        span.hits += hits
        span.misses += misses
        span.cache_read_secs += read_secs

    def _on_span(self, event: str, t_start: float, t_end: float,
                 fun_name: str = "", **_: Any) -> None:
        stage = _STAGES.get(event)
        if stage is None:
            return
        t = self._thread()
        top = t.open[-1] if t.open else None
        if (top is not None and top.stage == stage
                and top.t_start == t_start):
            span = t.open.pop()
        else:  # entered before the listeners were registered
            span = _OpenSpan(stage, fun_name, t_start)
        secs = max(t_end - t_start, 0.0)
        span.secs[stage + "_secs"] = secs  # covers its children's of it
        if t.open:
            parent = t.open[-1]
            parent.n_children += 1 + span.n_children
            for key, v in span.secs.items():
                parent.secs[key] += v
            parent.hits += span.hits
            parent.misses += span.misses
            parent.cache_read_secs += span.cache_read_secs
        else:
            self._file(t, span, secs)

    # ---- filing a program's own span ----

    def _row(self, fn: str) -> Dict[str, Any]:
        row = self.programs.get(fn)
        if row is None:
            row = self.programs[fn] = {
                "n_trace": 0, "n_lower": 0, "n_compile": 0, "n_children": 0,
                "hits": 0, "misses": 0, "trace_secs": 0.0, "lower_secs": 0.0,
                "compile_secs": 0.0, "cache_read_secs": 0.0, "max_secs": 0.0,
                "executables": [], "max_temp_bytes": None,
                "max_peak_bytes": None,
            }
        return row

    def _file(self, t, span: _OpenSpan, secs: float) -> None:
        fn = _program_name(span.fn)
        entry: Dict[str, Any] = {
            "fn": fn, "stage": span.stage,
            "t_start": round(span.t_start, 6), "secs": round(secs, 6),
            "thread": threading.current_thread().name,
            "n_children": span.n_children,
        }
        if span.stage == "compile":
            entry["cache"] = ("hit" if span.hits else
                              "miss" if span.misses else "uncached")
            entry["cache_read_secs"] = round(span.cache_read_secs, 6)
        # One compilation = a trace (where jax had none cached), a lower
        # and a compile, one after the other on one thread.
        running = secs + (0.0 if span.stage == "trace"
                          else t.compiling.get(fn, 0.0))
        record = None
        if span.stage == "compile":
            t.compiling.pop(fn, None)
            record = {"label": _label_of(fn), "cache": entry["cache"],
                      "secs": round(running, 6),
                      **dict.fromkeys(MEMORY_FIELDS)}
            entry["label"] = dict(record["label"])
            entry.update(dict.fromkeys(MEMORY_FIELDS))
        else:
            t.compiling[fn] = running
        with self._lock:
            row = self._row(fn)
            if record is not None:
                row["executables"].append(record)
            row["n_" + span.stage] += 1
            row["n_children"] += span.n_children
            for key, v in span.secs.items():
                row[key] += v
                self.secs[key] += v
            row["hits"] += span.hits
            row["misses"] += span.misses
            row["cache_read_secs"] += span.cache_read_secs
            row["max_secs"] = max(row["max_secs"], running)
            self.busy_secs += secs
            self.spans.append(entry)
        if record is not None:
            self._reconcile((_module_name(span.fn), fn, record, entry,
                             span.before))
        for watch in list(_WATCHES):
            watch._on_stage_span(entry)

    # ---- what the compiler says each executable needs ----

    def _alive(self) -> Optional[Dict[int, Any]]:
        """{id: fingerprint} of the executables the backend lists now;
        None where there is none to ask."""
        if self._live is None:
            return None
        t0 = time.perf_counter()
        try:
            return {id(exe): exe.fingerprint for exe in self._live()}
        except Exception as e:  # noqa: BLE001 — whatever the backend raises
            self._degrade(e)
            return None
        finally:
            with self._lock:
                self.executables_read_secs += time.perf_counter() - t0

    def _degrade(self, err: Exception) -> None:
        logger.warning(
            "the backend's executables give no memory statistics "
            f"({err!r}): the compile ledger's byte fields stay null")
        self._live = None
        self._pending = []

    def _born_since(self, live: list, before: Optional[Dict[int, Any]],
                    module: str, by_name: bool):
        """The oldest executable of ``live`` (newest first) that no record
        has claimed and that was not alive in ``before``; where that
        leaves several, or ``by_name``, the oldest whose module is
        ``module``. None: not listed (yet)."""
        born = []
        for exe in reversed(live):
            key, fingerprint = id(exe), exe.fingerprint
            if (self._claimed.get(key, _MISSING) != fingerprint
                    and (before is None
                         or before.get(key, _MISSING) != fingerprint)):
                born.append(exe)
        if len(born) == 1 and before is not None and not by_name:
            return born[0]
        for exe in born:
            known = self._names.get(id(exe))
            if known is None or known[0] != exe.fingerprint:
                known = self._names[id(exe)] = (
                    exe.fingerprint, exe.hlo_modules()[0].name)
            if known[1] == module:
                return exe
        return None

    def _reconcile(self, born: Optional[tuple] = None) -> None:
        """Give every record that waits for its executable (``born``: the
        one whose ``compile`` span just ended) the compiler's statistics
        of the executable born inside its span, in compile order. One
        that is not listed yet keeps waiting for the next call; a backend
        that gives no statistics is asked once."""
        if self._live is None or (born is None and not self._pending):
            return
        t0 = time.perf_counter()
        found = []
        with self._exe_lock:
            if self._live is None:
                return
            waiting = self._pending + ([born] if born is not None else [])
            self._pending = []
            try:
                live = self._live()
                for item in waiting:
                    # the record whose span just ended takes the one
                    # executable born in it; a record that waited, or
                    # one behind others, goes by the module's name
                    exe = self._born_since(
                        live, item[4], item[0],
                        by_name=item is not born or len(waiting) > 1)
                    if exe is None:
                        self._pending.append(item)
                        continue
                    stats = exe.get_compiled_memory_stats()
                    found.append((item, {
                        k: int(getattr(stats, attr))
                        for k, attr in MEMORY_FIELDS.items()}))
                    self._claimed[id(exe)] = exe.fingerprint
                alive = {id(exe) for exe in live}
                for table in (self._claimed, self._names):
                    for key in [k for k in table if k not in alive]:
                        del table[key]
            except Exception as e:  # noqa: BLE001 — whatever the backend raises
                self._degrade(e)
                return
        with self._lock:
            for (_, fn, record, entry, _), stats in found:
                record.update(stats)
                entry.update(stats)
                row = self.programs[fn]
                for key in ("temp_bytes", "peak_bytes"):
                    row["max_" + key] = max(row["max_" + key] or 0,
                                            stats[key])
                if (self.max_temp_bytes is None
                        or stats["temp_bytes"] > self.max_temp_bytes):
                    self.max_temp_bytes = stats["temp_bytes"]
                    self.max_temp_program = {"fn": fn,
                                             "label": record["label"]}
            self.executables_read_secs += time.perf_counter() - t0

    def executables(self, fn: str) -> list:
        """Copies of the executables' records of program ``fn``, in the
        order compiled."""
        self._reconcile()
        with self._lock:
            row = self.programs.get(fn)
            return [] if row is None else _copy_records(row["executables"])

    def as_dict(self) -> Dict[str, Any]:
        """Plain data (the drivers and ``/metrics.json`` dump it as JSON):
        the seven keys this has always had, ``busy_secs``, ``programs``
        and ``spans`` (oldest first; in each ``fn`` is its ``programs``
        key), then the executables' totals."""
        self._reconcile()
        with self._lock:
            return {
                "dir": compilation_cache_dir(), "hits": self.hits,
                "misses": self.misses,
                **{k: round(v, 3) for k, v in self.secs.items()},
                "busy_secs": round(self.busy_secs, 6),
                "programs": {
                    fn: {k: round(v, 6) if isinstance(v, float) else v
                         for k, v in row.items()}
                    | {"executables": _copy_records(row["executables"])}
                    for fn, row in self.programs.items()},
                "spans": _copy_records(self.spans),
                "max_temp_bytes": self.max_temp_bytes,
                "max_temp_program": self.max_temp_program and dict(
                    self.max_temp_program,
                    label=dict(self.max_temp_program["label"])),
                "executables_unmatched": len(self._pending),
                "executables_read_secs": round(
                    self.executables_read_secs, 6),
            }


def _copy_records(records) -> list:
    """Snapshots of ring entries or executables' records (a ``label`` is a
    flat dict of its own)."""
    return [dict(r, label=dict(r["label"])) if "label" in r else dict(r)
            for r in records]


_CACHE_STATS: Optional[CacheStats] = None
# The live CompileWatches (several generation servers share a process):
# each filed span also goes to their telemetry sinks.
_WATCHES: "weakref.WeakSet[CompileWatch]" = weakref.WeakSet()


def cache_stats() -> Optional[Dict[str, Any]]:
    """The compile ledger of this process so far (CacheStats.as_dict);
    None where :func:`enable_compilation_cache` never ran."""
    return _CACHE_STATS.as_dict() if _CACHE_STATS is not None else None


def executables(fn: str) -> list:
    """The ledger's records of program ``fn``'s executables, in the order
    compiled (CacheStats.executables); [] where no ledger runs."""
    return _CACHE_STATS.executables(fn) if _CACHE_STATS is not None else []


def enable_compilation_cache() -> None:
    """Arm JAX's persistent compilation cache for this process (launcher
    children, the benchmark's drivers and chip_smoke.py's phases all call
    this one helper) and start the compile ledger. jax reads
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
    _CACHE_STATS = CacheStats(jax_live_executables)
    jax.monitoring.register_scalar_listener(_CACHE_STATS._on_enter)
    jax.monitoring.register_event_time_span_listener(_CACHE_STATS._on_span)
    jax.monitoring.register_event_listener(_CACHE_STATS._on_event)
    jax.monitoring.register_event_duration_secs_listener(
        _CACHE_STATS._on_duration
    )


def thread_cache_counts() -> Tuple[int, int]:
    """The calling thread's (hits, misses) in the compile ledger; zeros
    where :func:`enable_compilation_cache` never ran."""
    return (_CACHE_STATS.thread_counts() if _CACHE_STATS is not None
            else (0, 0))


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
        hits0, misses0 = thread_cache_counts()
        t0 = self._watch._clock()
        try:
            return self._fn(*args, **kwargs)
        finally:
            secs = self._watch._clock() - t0
            hits, misses = thread_cache_counts()
            self._watch._compile_end(self._name, sig, secs,
                                     hits - hits0, misses - misses0)


class CompileWatch:
    """Process-wide (or per-server) compile-event registry.

    ``telemetry_sink`` is any Telemetry-like object (``inc`` /
    ``set_gauge`` / ``event`` / ``add_span``); ``clock`` is injectable
    for fake-clock tests. While it is installed the compile ledger hands
    it every program's stage spans (``compile/<stage>``, on the clock of
    the sink's other spans)."""

    enabled = True

    def __init__(self, telemetry_sink=None, *,
                 storm_warmup_calls: int = 16,
                 clock: Callable[[], float] = time.monotonic):
        self.tel = telemetry_sink if telemetry_sink is not None \
            else telemetry.get()
        self.storm_warmup_calls = max(int(storm_warmup_calls), 1)
        self._clock = clock
        self._lock = threading.Lock()
        self._fns: Dict[str, _FnRecord] = {}
        self._inflight = 0
        self._warned_storms: Set[str] = set()
        _WATCHES.add(self)

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

    def _compile_end(self, name: str, sig: str, secs: float,
                     cache_hits: int = 0, cache_misses: int = 0) -> None:
        """``cache_hits`` / ``cache_misses``: the compile ledger's counts
        on the calling thread around the observed call — jax's own."""
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
        if cache_hits:
            self.tel.inc("compile/cache_hits", float(cache_hits))
        if cache_misses:
            self.tel.inc("compile/cache_misses", float(cache_misses))

    def _on_stage_span(self, entry: Dict[str, Any]) -> None:
        attrs = {"fn": entry["fn"]}
        if "cache" in entry:
            attrs["cache"] = entry["cache"]
            # the executable's label and bytes, where it was found
            attrs.update(entry["label"])
            attrs.update({k: entry[k] for k in MEMORY_FIELDS
                          if entry[k] is not None})
        self.tel.add_span("compile/" + entry["stage"], entry["t_start"],
                          entry["secs"], **attrs)

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
        _WATCHES.discard(self)


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
              clock: Callable[[], float] = time.monotonic):
    """Install the process-global compile watch. A disabled (or absent)
    config keeps the null sink — jit sites never re-check."""
    global _GLOBAL
    if cfg is None or not getattr(cfg, "enabled", False):
        _GLOBAL = NULL
        return NULL
    _GLOBAL = CompileWatch(
        telemetry_sink,
        storm_warmup_calls=getattr(cfg, "storm_warmup_calls", 16),
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
