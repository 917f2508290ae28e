"""Compile & HBM observatory (base/compile_watch.py, system/memwatch.py,
docs/observability.md §Compile & memory).

Fake clocks + fake devices everywhere: compile timing is driven by an
injected monotonic clock the wrapped fn advances, HBM readings come from
injectable device fakes with scripted ``memory_stats()`` dicts — zero
real sleeps, no jax arrays, no backend dependence. The disabled contract
(scrape bit-identical with the observatory off) is pinned here, and the
sentinel's compile/HBM rule pack is validated through the same
``rules_from_config`` path the master runs.
"""

import json
import time

import pytest

from areal_tpu.api.train_config import CompileWatchConfig, SentinelConfig
from areal_tpu.base import compile_watch as cw
from areal_tpu.base import telemetry
from areal_tpu.system import memwatch as mw
from areal_tpu.system.sentinel import (
    COMPILE_RULES,
    DEFAULT_RULES,
    SentinelConfigError,
    parse_rules,
    rules_from_config,
)

pytestmark = pytest.mark.compilewatch


class Arr:
    """Array-like stand-in: compile_watch only reads .shape/.dtype."""

    def __init__(self, shape, dtype="float32"):
        self.shape = shape
        self.dtype = dtype


class FakeDevice:
    """jax device stand-in: memory_stats() returns a mutable dict."""

    def __init__(self, stats):
        self.stats = stats

    def memory_stats(self):
        return self.stats


def make_watch(**kw):
    """(watch, registry, clock dict) with a controllable monotonic."""
    reg = telemetry.TelemetryRegistry()
    t = {"now": 0.0}
    watch = cw.CompileWatch(reg, clock=lambda: t["now"], **kw)
    return watch, reg, t


# ---------------------------------------------------------------------------
# abstract signatures
# ---------------------------------------------------------------------------


def test_abstract_signature_keys_on_shape_dtype_and_statics():
    sig = lambda *a, **k: cw.abstract_signature(a, k)  # noqa: E731
    assert sig(Arr((4, 8))) == sig(Arr((4, 8)))
    assert sig(Arr((4, 8))) != sig(Arr((4, 9)))
    assert sig(Arr((4, 8))) != sig(Arr((4, 8), dtype="bfloat16"))
    # static arg VALUES key the jit cache, so they key the signature too
    assert sig(Arr((4, 8)), 128) != sig(Arr((4, 8)), 256)
    assert sig(x=1, y=2) == sig(y=2, x=1)  # kwargs order-insensitive
    # containers recurse; list vs tuple is a retrace in jax too
    assert sig([Arr((2,))]) != sig((Arr((2,)),))


# ---------------------------------------------------------------------------
# compile-event recording
# ---------------------------------------------------------------------------


def test_compile_events_recorded_with_fake_clock():
    watch, reg, t = make_watch()
    inflight_seen = []

    def fn(x):
        inflight_seen.append(watch.inflight())
        t["now"] += 2.5  # the fake "compile + first dispatch" wall time
        return x

    f = watch.wrap("train/grad", fn)
    f(Arr((4, 128)))
    snap = reg.snapshot(reset=False)
    assert snap["counters"]["compile/events{fn=train/grad}"] == 1.0
    assert snap["counters"]["compile/secs{fn=train/grad}"] == 2.5
    assert snap["gauges"]["compile/distinct_shapes{fn=train/grad}"] == 1.0
    # the gauge pulsed up during the call and is back to 0 after
    assert inflight_seen == [True]
    assert snap["gauges"]["compile/inflight"] == 0.0
    assert not watch.inflight()
    # a known signature is a cache hit: no new compile event
    f(Arr((4, 128)))
    snap = reg.snapshot(reset=False)
    assert snap["counters"]["compile/events{fn=train/grad}"] == 1.0
    # a new shape compiles again and bumps distinct_shapes
    f(Arr((4, 256)))
    snap = reg.snapshot(reset=False)
    assert snap["counters"]["compile/events{fn=train/grad}"] == 2.0
    assert snap["counters"]["compile/secs{fn=train/grad}"] == 5.0
    assert snap["gauges"]["compile/distinct_shapes{fn=train/grad}"] == 2.0
    assert watch.stats()["train/grad"] == {
        "calls": 3.0, "distinct_shapes": 2.0,
    }


def test_wrapper_passes_through_result_and_exceptions():
    watch, reg, t = make_watch()
    f = watch.wrap("train/apply", lambda x, s: (x, s))
    a = Arr((2, 2))
    assert f(a, s=7) == (a, 7)
    assert f.__wrapped__(a, s=7) == (a, 7)

    def boom(x):
        raise RuntimeError("compile blew up")

    g = watch.wrap("train/boom", boom)
    with pytest.raises(RuntimeError, match="blew up"):
        g(Arr((1,)))
    # the inflight gauge must unwind even on an exception mid-compile
    assert not watch.inflight()
    assert reg.snapshot(reset=False)["gauges"]["compile/inflight"] == 0.0


# ---------------------------------------------------------------------------
# recompile-storm detection
# ---------------------------------------------------------------------------


def test_storm_fires_only_after_shape_stability(monkeypatch):
    warned = []
    monkeypatch.setattr(cw.logger, "warning", warned.append)
    watch, reg, t = make_watch(storm_warmup_calls=4)
    f = watch.wrap("gen/prefill", lambda x: x)
    stable = Arr((8, 512))
    f(stable)  # cold-start compile: never a storm
    counters = reg.snapshot(reset=False)["counters"]
    assert "compile/storm_events" not in counters
    # a second new shape BEFORE warmup stability: still churn, not storm
    f(Arr((8, 640)))
    assert "compile/storm_events" not in \
        reg.snapshot(reset=False)["counters"]
    # now hold shape-stable through the warmup window...
    for _ in range(4):
        f(stable)
    # ...then a new shape is exactly the storm signature
    f(Arr((8, 768)))
    counters = reg.snapshot(reset=False)["counters"]
    assert counters["compile/storm_events"] == 1.0
    assert len(warned) == 1 and "recompile storm" in warned[0]
    # the next new shape arrives with calls_since_new_sig reset: no storm
    f(Arr((8, 896)))
    assert reg.snapshot(reset=False)["counters"][
        "compile/storm_events"] == 1.0
    # stability then another new shape storms again (counted, warned once
    # per offending signature)
    for _ in range(4):
        f(stable)
    f(Arr((8, 1024)))
    assert reg.snapshot(reset=False)["counters"][
        "compile/storm_events"] == 2.0


def test_fresh_wrappers_recompiling_known_shapes_are_not_storms():
    """The reshard identity pattern: a NEW jit object per publish group
    recompiles shapes the per-name ledger already saw. That is a real
    compile (events count) but not shape churn (no storm)."""
    watch, reg, t = make_watch(storm_warmup_calls=2)
    shape = Arr((16, 1024))
    for i in range(6):
        f = watch.wrap("reshard/identity", lambda x: x)
        f(shape)
        f(shape)  # warm call on the same wrapper
    snap = reg.snapshot(reset=False)
    # every fresh wrapper's first call recorded as a compile event...
    assert snap["counters"]["compile/events{fn=reshard/identity}"] == 6.0
    # ...but the name-level shape set never grew past 1, and no storm
    assert snap["gauges"][
        "compile/distinct_shapes{fn=reshard/identity}"] == 1.0
    assert "compile/storm_events" not in snap["counters"]


# ---------------------------------------------------------------------------
# the compile ledger (CacheStats): jax's monitoring events, fed by hand
# ---------------------------------------------------------------------------

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"
READ = "/jax/compilation_cache/cache_retrieval_time_sec"
OLD_KEYS = ["dir", "hits", "misses", "trace_secs", "lower_secs",
            "compile_secs", "cache_read_secs"]
OLD_ROW_KEYS = ["n_trace", "n_lower", "n_compile", "n_children", "hits",
                "misses", "trace_secs", "lower_secs", "compile_secs",
                "cache_read_secs", "max_secs"]
NEW_TOTALS = {"max_temp_bytes", "max_temp_program", "executables_unmatched",
              "executables_read_secs"}


class Feed:
    """Sends a ledger what jax's ``dispatch.log_elapsed_time`` sends: a
    scalar when a stage is entered, a duration and a time span when it is
    left (the ledger listens to the first and the last)."""

    def __init__(self, ledger):
        self.ledger = ledger

    def enter(self, event, fn, t_start):
        self.ledger._on_enter(event, t_start, fun_name=fn)

    def leave(self, event, fn, t_start, t_end):
        self.ledger._on_duration(event, t_end - t_start, fun_name=fn)
        self.ledger._on_span(event, t_start, t_end, fun_name=fn)

    def stage(self, event, fn, t_start, t_end, inside=()):
        self.enter(event, fn, t_start)
        for step in inside:
            step()
        self.leave(event, fn, t_start, t_end)

    def hit(self, read_secs):
        self.ledger._on_event(HIT)
        self.ledger._on_duration(READ, read_secs)

    def miss(self):
        self.ledger._on_event(MISS)

    def program(self, fn, t, trace=1.0, lower=0.5, compile=2.0,
                read_secs=None, inside=()):
        """One compilation of ``fn`` from time ``t``: a miss, or a hit
        that took ``read_secs`` to read; ``inside`` the compile call,
        more steps (the backend gives birth to the executable there);
        returns when it ended."""
        outcome = (self.miss if read_secs is None
                   else lambda: self.hit(read_secs))
        self.stage(TRACE, fn, t, t + trace)
        t += trace
        self.stage(LOWER, f"jit({fn})", t, t + lower)
        t += lower
        self.stage(COMPILE, f"jit({fn})", t, t + compile,
                   [outcome, *inside])
        return t + compile


def test_ledger_files_a_program_by_name_with_its_outcome():
    ledger = cw.CacheStats()
    feed = Feed(ledger)
    t = feed.program("train_apply", 100.0)
    feed.program("train_apply", t, trace=0.25, lower=0.25, compile=0.5,
                 read_secs=0.125)
    d = ledger.as_dict()
    row = d["programs"]["train_apply"]
    assert (row["n_trace"], row["n_lower"], row["n_compile"]) == (2, 2, 2)
    assert (row["hits"], row["misses"]) == (1, 1) == (d["hits"], d["misses"])
    assert row["trace_secs"] == 1.25 and row["lower_secs"] == 0.75
    assert row["compile_secs"] == 2.5 and row["cache_read_secs"] == 0.125
    assert row["max_secs"] == 3.5  # the first compilation, not the sum
    assert d["busy_secs"] == 4.5
    assert [(e["fn"], e["stage"], e["t_start"], e["secs"])
            for e in d["spans"][:3]] == [
        ("train_apply", "trace", 100.0, 1.0),
        ("train_apply", "lower", 101.0, 0.5),
        ("train_apply", "compile", 101.5, 2.0)]
    first, second = d["spans"][2], d["spans"][5]
    assert first["cache"] == "miss" and first["cache_read_secs"] == 0.0
    assert second["cache"] == "hit" and second["cache_read_secs"] == 0.125
    assert "cache" not in d["spans"][0]
    # a backend compile the cache never saw (jax gave it no key)
    feed.stage(COMPILE, "jit(callback_fn)", 200.0, 201.0)
    assert ledger.as_dict()["spans"][-1]["cache"] == "uncached"


def test_ledger_counts_nested_spans_once_and_files_them_under_the_program():
    ledger = cw.CacheStats()
    feed = Feed(ledger)
    # train_grad_sliced's trace calls a jitted inner twice, which calls
    # sin; lowering it traces one more helper.
    inner1 = lambda: feed.stage(  # noqa: E731
        TRACE, "inner", 10.5, 11.5,
        [lambda: feed.stage(TRACE, "sin", 10.75, 11.0)])
    inner2 = lambda: feed.stage(TRACE, "inner", 12.0, 13.0)  # noqa: E731
    feed.stage(TRACE, "train_grad_sliced", 10.0, 14.0, [inner1, inner2])
    feed.stage(LOWER, "jit(train_grad_sliced)", 14.0, 15.0,
               [lambda: feed.stage(TRACE, "helper", 14.25, 14.5)])
    feed.stage(COMPILE, "jit(train_grad_sliced)", 15.0, 17.0, [feed.miss])
    d = ledger.as_dict()
    assert sorted(d["programs"]) == ["train_grad_sliced"]
    row = d["programs"]["train_grad_sliced"]
    assert row["n_trace"] == 1 and row["n_children"] == 4
    # the plain sum of the trace events would be 4 + 1 + 0.25 + 1 + 0.25
    assert d["trace_secs"] == 4.25 == row["trace_secs"]
    assert d["lower_secs"] == 1.0 and d["compile_secs"] == 2.0
    # the helper traced while lowering is in two stage unions, once here
    assert d["busy_secs"] == 7.0
    assert row["max_secs"] == 7.0
    assert [e["n_children"] for e in d["spans"]] == [3, 1, 0]


def test_ledger_cache_traffic_lands_on_its_own_threads_compile_span():
    import threading

    ledger = cw.CacheStats()
    feed = Feed(ledger)
    other_in_compile = threading.Event()
    main_done = threading.Event()

    def other():
        feed.enter(COMPILE, "jit(decode)", 50.0)
        other_in_compile.set()
        assert main_done.wait(10)
        feed.hit(0.25)
        feed.leave(COMPILE, "jit(decode)", 50.0, 53.0)

    th = threading.Thread(target=other, name="gen-0")
    th.start()
    assert other_in_compile.wait(10)
    # while gen-0 sits in its compile, this thread's compile misses
    feed.stage(COMPILE, "jit(prefill)", 51.0, 52.0, [feed.miss])
    assert ledger.thread_counts() == (0, 1)
    main_done.set()
    th.join(10)
    assert not th.is_alive()
    d = ledger.as_dict()
    by_fn = {e["fn"]: e for e in d["spans"]}
    assert by_fn["prefill"]["cache"] == "miss"
    assert by_fn["prefill"]["cache_read_secs"] == 0.0
    assert by_fn["decode"]["cache"] == "hit"
    assert by_fn["decode"]["cache_read_secs"] == 0.25
    assert by_fn["decode"]["thread"] == "gen-0"
    assert d["programs"]["prefill"]["misses"] == 1
    assert d["programs"]["prefill"]["hits"] == 0
    assert d["programs"]["decode"]["hits"] == 1
    # per thread, summed: two threads compiled at once
    assert d["compile_secs"] == 4.0 == d["busy_secs"]
    # traffic no span claims still reaches the totals, under one name
    ledger._on_event(HIT)
    d = ledger.as_dict()
    assert d["hits"] == 2
    assert d["programs"][cw.UNNAMED_PROGRAM]["hits"] == 1


def test_ledger_ring_drops_the_oldest_and_the_aggregates_keep_counting():
    ledger = cw.CacheStats()
    feed = Feed(ledger)
    n = cw.SPAN_RING + 100
    for i in range(n):
        feed.stage(COMPILE, f"jit(step{i % 7})", float(i), i + 0.5,
                   [feed.miss])
    d = ledger.as_dict()
    assert len(d["spans"]) == cw.SPAN_RING
    assert d["spans"][0]["t_start"] == 100.0  # oldest first, 100 dropped
    assert d["spans"][-1]["t_start"] == float(n - 1)
    assert sum(r["n_compile"] for r in d["programs"].values()) == n
    assert d["misses"] == n == sum(
        r["misses"] for r in d["programs"].values())
    assert d["compile_secs"] == d["busy_secs"] == 0.5 * n
    # a span that was entered before the ledger listened still counts
    ledger._on_span(TRACE, 5000.0, 5001.0, fun_name="late")
    assert ledger.as_dict()["programs"]["late"]["trace_secs"] == 1.0


def test_ledger_loses_no_update_under_many_threads():
    import sys
    import threading

    ledger = cw.CacheStats()
    n_threads, n_programs = 16, 150
    start = threading.Barrier(n_threads)

    def worker(k):
        feed = Feed(ledger)
        start.wait(10)
        t = 1000.0 * k
        for i in range(n_programs):
            t = feed.program(f"step{i % 5}", t, trace=0.5, lower=0.25,
                             compile=1.0,
                             read_secs=(0.125 if i % 2 else None))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=worker, args=(k,))
                   for k in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(th.is_alive() for th in threads)
    d = ledger.as_dict()
    n = n_threads * n_programs
    assert (d["hits"], d["misses"]) == (n // 2, n // 2)
    assert sum(r["n_compile"] for r in d["programs"].values()) == n
    assert sum(r["hits"] for r in d["programs"].values()) == n // 2
    assert d["trace_secs"] == 0.5 * n and d["compile_secs"] == 1.0 * n
    assert d["busy_secs"] == 1.75 * n
    assert d["cache_read_secs"] == 0.125 * (n // 2)
    assert all(r["max_secs"] == 1.75 for r in d["programs"].values())
    assert len(d["spans"]) == cw.SPAN_RING


def test_ledger_totals_are_the_sums_over_programs():
    ledger = cw.CacheStats()
    feed = Feed(ledger)
    t = 0.0
    for i, fn in enumerate(["opt_init", "infer_forward", "train_apply",
                            "infer_forward", "add"]):
        t = feed.program(fn, t, trace=0.5 + i, lower=0.25, compile=1.0 + i,
                         read_secs=(0.5 if i % 2 else None))
    d = ledger.as_dict()
    for key in OLD_KEYS[1:]:
        assert d[key] == pytest.approx(
            sum(r[key] for r in d["programs"].values())), key
    assert d["programs"]["infer_forward"]["n_compile"] == 2
    assert d["busy_secs"] == pytest.approx(
        d["trace_secs"] + d["lower_secs"] + d["compile_secs"])


def test_cache_stats_keeps_its_keys_and_is_plain_data(monkeypatch):
    assert cw.cache_stats() is None or set(OLD_KEYS) <= set(cw.cache_stats())
    ledger = cw.CacheStats()
    monkeypatch.setattr(cw, "_CACHE_STATS", ledger)
    Feed(ledger).program("adv_prep", 1.5e9, read_secs=0.01)
    d = cw.cache_stats()
    assert list(d)[:10] == OLD_KEYS + ["busy_secs", "programs", "spans"]
    assert set(d) == set(list(d)[:10]) | NEW_TOTALS
    assert list(d["programs"]["adv_prep"])[:11] == OLD_ROW_KEYS
    assert set(d["programs"]["adv_prep"]) == set(OLD_ROW_KEYS) | {
        "executables", "max_temp_bytes", "max_peak_bytes"}
    assert json.loads(json.dumps(d)) == d
    # a snapshot: what the ledger files later does not reach into it
    Feed(ledger).program("adv_prep", 1.5e9 + 10)
    assert len(d["spans"]) == 3 and d["programs"]["adv_prep"]["misses"] == 0


def test_cache_hits_and_misses_come_from_the_ledger(monkeypatch):
    """The watch's ``compile/cache_hits|misses`` are jax's own events as
    the ledger counted them on the calling thread around the observed
    call (no listing of the cache directory)."""
    ledger = cw.CacheStats()
    monkeypatch.setattr(cw, "_CACHE_STATS", ledger)
    feed = Feed(ledger)
    watch, reg, t = make_watch()

    def cold(x):  # XLA really compiled: jax reports a miss
        feed.program("train_grad_sliced", 10.0)
        return x

    f = watch.wrap("train/grad", cold)
    f(Arr((4, 128)))
    counters = reg.snapshot(reset=False)["counters"]
    assert counters["compile/cache_misses"] == 1.0
    assert "compile/cache_hits" not in counters

    def warm(x):  # two executables read back from the cache
        end = feed.program("train_grad_sliced", 20.0, read_secs=0.1)
        feed.program("train_apply", end, read_secs=0.1)
        return x

    g = watch.wrap("train/grad", warm)
    g(Arr((4, 128)))
    counters = reg.snapshot(reset=False)["counters"]
    assert counters["compile/cache_misses"] == 1.0
    assert counters["compile/cache_hits"] == 2.0
    # a first call that compiled nothing (jax had the executable) adds none
    h = watch.wrap("train/grad", lambda x: x)
    h(Arr((4, 128)))
    assert reg.snapshot(reset=False)["counters"] == counters | {
        "compile/events{fn=train/grad}": 3.0}
    # with no ledger armed the watch still counts its compile events
    monkeypatch.setattr(cw, "_CACHE_STATS", None)
    watch.wrap("train/grad", lambda x: x)(Arr((4, 128)))


def test_stage_spans_reach_the_watchs_telemetry_and_only_while_it_lives():
    ledger = cw.CacheStats()
    feed = Feed(ledger)
    watch, reg, t = make_watch()
    feed.program("infer_forward", 1000.0, read_secs=0.05)
    spans = [s for s in reg.snapshot(reset=False)["spans"]
             if s["name"].startswith("compile/")]
    assert [(s["name"], s["t_start"], s["dur_secs"]) for s in spans] == [
        ("compile/trace", 1000.0, 1.0), ("compile/lower", 1001.0, 0.5),
        ("compile/compile", 1001.5, 2.0)]
    assert [s["attrs"] for s in spans] == [
        {"fn": "infer_forward"}, {"fn": "infer_forward"},
        {"fn": "infer_forward", "cache": "hit"}]
    watch.close()
    feed.program("infer_forward", 2000.0)
    assert len([s for s in reg.snapshot(reset=False)["spans"]
                if s["name"].startswith("compile/")]) == 3


# ---------------------------------------------------------------------------
# the compile ledger under jax itself (CPU, a cache directory of the test's)
# ---------------------------------------------------------------------------


@pytest.fixture
def jax_ledger(tmp_path):
    """A ledger listening to this process's jax, and the persistent cache
    in a directory of the test's own; both undone afterwards."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    ledger = cw.CacheStats(cw.jax_live_executables)
    jax.monitoring.register_scalar_listener(ledger._on_enter)
    jax.monitoring.register_event_time_span_listener(ledger._on_span)
    jax.monitoring.register_event_listener(ledger._on_event)
    jax.monitoring.register_event_duration_secs_listener(ledger._on_duration)
    keys = ["jax_compilation_cache_dir", "jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes"]
    before = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cc.reset_cache()
    try:
        yield ledger
    finally:
        jax.monitoring.unregister_scalar_listener(ledger._on_enter)
        jax.monitoring.unregister_event_time_span_listener(ledger._on_span)
        jax.monitoring.unregister_event_listener(ledger._on_event)
        jax.monitoring.unregister_event_duration_listener(
            ledger._on_duration)
        for k, v in before.items():
            jax.config.update(k, v)
        cc.reset_cache()


def test_ledger_under_jax_miss_then_hit_then_nothing(jax_ledger):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def inner(x):
        return jnp.sin(x) * 2.0

    def ledger_probe_program(x):
        return (inner(x) + inner(x * x)).sum()

    step = jax.jit(ledger_probe_program)
    x = jnp.arange(8, dtype=jnp.float32)
    t0 = time.time()
    step(x).block_until_ready()
    d = jax_ledger.as_dict()
    row = d["programs"]["ledger_probe_program"]
    assert (row["n_trace"], row["n_lower"], row["n_compile"]) == (1, 1, 1)
    assert (row["hits"], row["misses"]) == (0, 1)
    assert row["n_children"] >= 2  # inner (and what it calls) folded in
    assert "inner" not in d["programs"] and "sin" not in d["programs"]
    mine = [e for e in d["spans"] if e["fn"] == "ledger_probe_program"]
    assert [e["stage"] for e in mine] == ["trace", "lower", "compile"]
    assert mine[2]["cache"] == "miss"
    assert all(t0 <= e["t_start"] <= time.time() for e in mine)
    assert row["max_secs"] == pytest.approx(
        sum(e["secs"] for e in mine), abs=1e-4)
    # the same program after jax forgot its executables: read back
    jax.clear_caches()
    step(x).block_until_ready()
    d = jax_ledger.as_dict()
    row = d["programs"]["ledger_probe_program"]
    assert (row["n_compile"], row["hits"], row["misses"]) == (2, 1, 1)
    last = [e for e in d["spans"] if e["fn"] == "ledger_probe_program"][-1]
    assert last["stage"] == "compile" and last["cache"] == "hit"
    assert last["cache_read_secs"] > 0
    assert row["cache_read_secs"] > 0
    # a warm call sends nothing
    n_spans, busy = len(d["spans"]), d["busy_secs"]
    step(x).block_until_ready()
    d = jax_ledger.as_dict()
    assert len(d["spans"]) == n_spans and d["busy_secs"] == busy
    # every filed span is some program's own: the totals are their sums
    for key in ("hits", "misses"):
        assert d[key] == sum(r[key] for r in d["programs"].values())
    assert json.loads(json.dumps(d)) == d


# ---------------------------------------------------------------------------
# one record per executable: the label and the compiler's statistics
# ---------------------------------------------------------------------------


class FakeStats:
    def __init__(self, temp, peak=None):
        self.temp_size_in_bytes = temp
        self.argument_size_in_bytes = 16
        self.output_size_in_bytes = 8
        self.alias_size_in_bytes = 4
        self.generated_code_size_in_bytes = 2
        self.peak_memory_in_bytes = temp + 20 if peak is None else peak


class FakeExecutable:
    """LoadedExecutable stand-in: a module name, a fingerprint and what
    ``get_compiled_memory_stats()`` answers (an exception is raised)."""

    def __init__(self, name, stats, fingerprint=None):
        self.name, self.stats = name, stats
        self.fingerprint = fingerprint or name.encode()
        self.asked = self.parsed = 0

    def hlo_modules(self):
        self.parsed += 1
        return [self]

    def get_compiled_memory_stats(self):
        self.asked += 1
        if isinstance(self.stats, Exception):
            raise self.stats
        return self.stats


class FakeBackend:
    """``live`` is the client's list, newest first like jax's."""

    def __init__(self):
        self.live, self.calls = [], 0

    def __call__(self):
        self.calls += 1
        return list(self.live)

    def born(self, *exes):
        self.live[:0] = reversed(exes)

    def bears(self, *exes):
        """A step for ``Feed.program(inside=...)``: the compile call
        leaves these executables behind."""
        return [lambda: self.born(*exes)]


BYTES = list(cw.MEMORY_FIELDS)


def test_executable_record_holds_label_outcome_and_bytes():
    backend = FakeBackend()
    ledger = cw.CacheStats(backend)
    feed = Feed(ledger)
    cw.label("train_grad_sliced", grid="2x128", remat="full",
             reckoned_heap_bytes=1000)
    backend.born(FakeExecutable("jit_sin", FakeStats(9)))  # lived before
    end = feed.program("train_grad_sliced", 10.0, inside=backend.bears(
        FakeExecutable("jit_train_grad_sliced", FakeStats(700))))
    feed.program("convert_element_type", end, read_secs=0.1,
                 inside=backend.bears(
                     FakeExecutable("jit_convert_element_type", FakeStats(0))))
    d = ledger.as_dict()
    (rec,) = d["programs"]["train_grad_sliced"]["executables"]
    assert rec == {
        "label": {"grid": "2x128", "remat": "full",
                  "reckoned_heap_bytes": 1000},
        "cache": "miss", "secs": 3.5, "temp_bytes": 700,
        "argument_bytes": 16, "output_bytes": 8, "alias_bytes": 4,
        "code_bytes": 2, "peak_bytes": 720}
    # a compile of another program does not read the label
    (other,) = d["programs"]["convert_element_type"]["executables"]
    assert other["label"] == {} and other["cache"] == "hit"
    assert other["temp_bytes"] == 0
    row = d["programs"]["train_grad_sliced"]
    assert (row["max_temp_bytes"], row["max_peak_bytes"]) == (700, 720)
    assert d["max_temp_bytes"] == 700
    assert d["max_temp_program"] == {"fn": "train_grad_sliced",
                                     "label": rec["label"]}
    assert d["executables_unmatched"] == 0
    # the ring's entry of that compile span carries the same fields
    ring = [e for e in d["spans"] if e["fn"] == "train_grad_sliced"
            and e["stage"] == "compile"][0]
    assert {k: ring[k] for k in ["label"] + BYTES} == {
        k: rec[k] for k in ["label"] + BYTES}
    assert all("label" not in e for e in d["spans"]
               if e["stage"] != "compile")
    assert json.loads(json.dumps(d)) == d


def test_one_name_at_two_grids_gives_two_records_in_compile_order():
    backend = FakeBackend()
    ledger = cw.CacheStats(backend)
    feed = Feed(ledger)
    t = 0.0
    for grid, temp in (("1x256", 300), ("1x512", 900), ("1x128", 100)):
        cw.label("infer_forward", grid=grid, use_lp=True)
        t = feed.program("infer_forward", t, inside=backend.bears(
            FakeExecutable("jit_infer_forward", FakeStats(temp),
                           fingerprint=grid.encode())))
    assert [(r["label"]["grid"], r["temp_bytes"])
            for r in ledger.executables("infer_forward")] == [
        ("1x256", 300), ("1x512", 900), ("1x128", 100)]
    d = ledger.as_dict()
    assert d["programs"]["infer_forward"]["max_temp_bytes"] == 900
    assert d["max_temp_program"]["label"]["grid"] == "1x512"
    # every executable was read once, however often the list was walked,
    # and none was parsed for its name: its birth in the span says whose
    assert [exe.asked for exe in backend.live] == [1, 1, 1]
    assert [exe.parsed for exe in backend.live] == [0, 0, 0]
    # a snapshot: the caller's copy is its own
    ledger.executables("infer_forward")[0]["label"]["grid"] = "mine"
    assert ledger.executables("infer_forward")[0]["label"]["grid"] == "1x256"
    assert ledger.executables("never_compiled") == []


def test_label_stays_on_its_thread_and_the_next_store_replaces_it():
    import threading

    ledger = cw.CacheStats()
    feed = Feed(ledger)
    cw.label("train_grad_sliced", grid="4x64")
    worker = threading.Thread(
        target=lambda: feed.program("train_grad_sliced", 0.0))
    worker.start()
    worker.join()
    feed.program("train_grad_sliced", 10.0)
    cw.label("train_grad_sliced", grid="8x64", remat=False)
    feed.program("train_grad_sliced", 20.0)
    cw.label("infer_forward", grid="8x64")  # the stale label is gone
    feed.program("train_grad_sliced", 30.0)
    assert [r["label"] for r in ledger.executables("train_grad_sliced")] == [
        {}, {"grid": "4x64"}, {"grid": "8x64", "remat": False}, {}]


def test_several_born_in_one_span_are_told_apart_by_module_name():
    """Another thread's compile ends inside this one's: two executables
    are new when the span ends, and only then is a name read."""
    backend = FakeBackend()
    ledger = cw.CacheStats(backend)
    theirs = FakeExecutable("jit_infer_forward", FakeStats(11))
    mine = FakeExecutable("jit_train_apply", FakeStats(22))
    Feed(ledger).program("train_apply", 0.0,
                         inside=backend.bears(theirs, mine))
    (rec,) = ledger.executables("train_apply")
    assert rec["temp_bytes"] == 22
    assert (theirs.asked, mine.asked) == (0, 1)
    assert (theirs.parsed, mine.parsed) == (1, 1)
    # the other one is still there for its own record, its name kept
    Feed(ledger).program("infer_forward", 10.0)
    assert ledger.executables("infer_forward")[0]["temp_bytes"] is None
    ledger._pending[0] = ledger._pending[0][:4] + (None,)  # span unseen
    assert ledger.executables("infer_forward")[0]["temp_bytes"] == 11
    assert theirs.parsed == 1 and ledger.as_dict()[
        "executables_unmatched"] == 0


def test_threads_compiling_at_once_each_get_their_own_executable():
    """More threads than cores compile programs of their own names at
    once: births interleave inside each other's spans, and every record
    still ends with its own executable's bytes, claimed once."""
    import sys
    import threading

    backend = FakeBackend()
    lock = threading.Lock()
    ledger = cw.CacheStats(backend)
    n_threads, n_programs = 12, 40
    start = threading.Barrier(n_threads)

    def worker(k):
        feed = Feed(ledger)
        start.wait(10)
        t = 1000.0 * k
        for i in range(n_programs):
            exe = FakeExecutable(f"jit_step{k}", FakeStats(1000 * k + i),
                                 fingerprint=f"{k}/{i}".encode())

            def bear(exe=exe):
                with lock:  # the client's list is its own to keep whole
                    backend.born(exe)

            cw.label(f"step{k}", i=i)
            t = feed.program(f"step{k}", t, inside=[bear])

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=worker, args=(k,))
                   for k in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(th.is_alive() for th in threads)
    d = ledger.as_dict()
    assert d["executables_unmatched"] == 0
    for k in range(n_threads):
        assert [(r["label"]["i"], r["temp_bytes"]) for r in
                d["programs"][f"step{k}"]["executables"]] == [
            (i, 1000 * k + i) for i in range(n_programs)]
    assert all(exe.asked == 1 for exe in backend.live)
    assert d["max_temp_bytes"] == 1000 * (n_threads - 1) + n_programs - 1


def test_executable_listed_late_is_reconciled_in_compile_order():
    """The client lists an executable only after the span's listener ran:
    the record waits, the next dump finds it; one that never shows keeps
    its nulls and is counted."""
    backend = FakeBackend()
    ledger = cw.CacheStats(backend)
    feed = Feed(ledger)
    t = 0.0
    for grid in ("1x64", "1x128", "1x256"):
        cw.label("train_grad_sliced", grid=grid)
        t = feed.program("train_grad_sliced", t)
    d = ledger.as_dict()
    assert d["executables_unmatched"] == 3 and d["max_temp_bytes"] is None
    assert all(r["temp_bytes"] is None
               for r in d["programs"]["train_grad_sliced"]["executables"])
    # two of the three show up, oldest first in the client's list
    backend.born(FakeExecutable("jit_train_grad_sliced", FakeStats(64),
                                fingerprint=b"a"),
                 FakeExecutable("jit_train_grad_sliced", FakeStats(128),
                                fingerprint=b"b"),
                 FakeExecutable("jit_sin", FakeStats(1)))  # nobody's
    d = ledger.as_dict()
    assert [(r["label"]["grid"], r["temp_bytes"]) for r in
            d["programs"]["train_grad_sliced"]["executables"]] == [
        ("1x64", 64), ("1x128", 128), ("1x256", None)]
    assert d["executables_unmatched"] == 1 and d["max_temp_bytes"] == 128
    ring = [e["temp_bytes"] for e in d["spans"] if e["stage"] == "compile"]
    assert ring == [64, 128, None]
    assert json.loads(json.dumps(d)) == d
    assert d["executables_read_secs"] > 0


@pytest.mark.parametrize("answer", [RuntimeError("UNIMPLEMENTED"), None],
                         ids=["raises", "none"])
def test_backend_without_statistics_leaves_nulls_and_is_asked_once(answer):
    backend = FakeBackend()
    ledger = cw.CacheStats(backend)
    feed = Feed(ledger)
    exe = FakeExecutable("jit_train_apply", answer)
    t = feed.program("train_apply", 0.0, inside=backend.bears(exe))
    calls = backend.calls
    feed.program("train_apply", t, inside=backend.bears(
        FakeExecutable("jit_train_apply", answer, fingerprint=b"2")))
    d = ledger.as_dict()
    assert exe.asked == 1 and backend.calls == calls
    assert backend.live[0].asked == 0
    recs = d["programs"]["train_apply"]["executables"]
    assert len(recs) == 2
    assert all(r[k] is None for r in recs for k in BYTES)
    assert d["programs"]["train_apply"]["max_temp_bytes"] is None
    assert d["max_temp_bytes"] is None and d["executables_unmatched"] == 0
    assert json.loads(json.dumps(d)) == d


def test_ledger_without_a_backend_files_records_and_asks_nothing():
    ledger = cw.CacheStats()
    cw.label("opt_init", note="x")
    Feed(ledger).program("opt_init", 0.0)
    (rec,) = ledger.executables("opt_init")
    assert rec["label"] == {"note": "x"} and rec["cache"] == "miss"
    assert all(rec[k] is None for k in BYTES)
    assert ledger.as_dict()["executables_unmatched"] == 0


def test_stage_span_attrs_carry_the_executables_label_and_bytes():
    backend = FakeBackend()
    ledger = cw.CacheStats(backend)
    watch, reg, t = make_watch()
    cw.label("infer_forward", grid="1x64", hook=True)
    Feed(ledger).program(
        "infer_forward", 1000.0, read_secs=0.05, inside=backend.bears(
            FakeExecutable("jit_infer_forward", FakeStats(5))))
    spans = [s for s in reg.snapshot(reset=False)["spans"]
             if s["name"] == "compile/compile"]
    assert [s["attrs"] for s in spans] == [{
        "fn": "infer_forward", "cache": "hit", "grid": "1x64", "hook": True,
        "temp_bytes": 5, "argument_bytes": 16, "output_bytes": 8,
        "alias_bytes": 4, "code_bytes": 2, "peak_bytes": 25}]
    watch.close()


def test_executables_under_jax_miss_then_hit_give_the_same_bytes(jax_ledger):
    import jax
    import jax.numpy as jnp

    def ledger_bytes_probe(x):
        return (x @ x).sum() + jnp.tanh(x @ x.T).sum()

    step = jax.jit(ledger_bytes_probe)
    cw.label("ledger_bytes_probe", grid="64x64")
    step(jnp.ones((64, 64), jnp.float32)).block_until_ready()
    cw.label("ledger_bytes_probe", grid="32x32")
    step(jnp.ones((32, 32), jnp.float32)).block_until_ready()
    # The two executables stay alive behind jax's back: freed, the first
    # one's address can be handed to the one read back, which is the same
    # program (same fingerprint), and the ledger — it knows an executable
    # by both — then takes the newcomer for the one it has already
    # claimed (``executables_unmatched`` 1; seen under six workers, ROADMAP
    # D12, a ``tracing`` issue's to cure in ``CacheStats._born_since``).
    kept = cw.jax_live_executables()
    jax.clear_caches()  # jax forgets its executables: read back
    cw.label("ledger_bytes_probe", grid="64x64")
    step(jnp.ones((64, 64), jnp.float32)).block_until_ready()
    d = jax_ledger.as_dict()
    row = d["programs"]["ledger_bytes_probe"]
    big, small, again = row["executables"]
    assert [r["cache"] for r in row["executables"]] == ["miss", "miss", "hit"]
    assert [r["label"]["grid"] for r in row["executables"]] == [
        "64x64", "32x32", "64x64"]
    for r in (big, small):
        assert r["temp_bytes"] > 0 and r["argument_bytes"] > 0
        assert r["peak_bytes"] > 0
    assert big["argument_bytes"] == 64 * 64 * 4 == 4 * small["argument_bytes"]
    assert big["temp_bytes"] > small["temp_bytes"]
    assert {k: again[k] for k in BYTES} == {k: big[k] for k in BYTES}
    assert row["max_temp_bytes"] == big["temp_bytes"]
    assert row["n_compile"] == 3 and d["executables_unmatched"] == 0
    # found when the span ended: the ring's entries have their bytes
    ring = [e for e in d["spans"] if e["fn"] == "ledger_bytes_probe"
            and e["stage"] == "compile"]
    assert [e["temp_bytes"] for e in ring] == [
        r["temp_bytes"] for r in row["executables"]]
    assert json.loads(json.dumps(d)) == d
    del kept


def test_perf_probe_tabulates_the_ledgers_executables_largest_first():
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "perf_probe.py")
    spec = importlib.util.spec_from_file_location("_perf_probe_cw", path)
    probe = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(probe)
    backend = FakeBackend()
    ledger = cw.CacheStats(backend)
    feed = Feed(ledger)
    t = 0.0
    for fn, temp, label in [("prefill", 2_000_000_000, dict(bucket=512)),
                            ("decode", 3_500_000_000, dict(bucket=64)),
                            ("never_listed", None, {})]:
        cw.label(fn, **label)
        t = feed.program(fn, t, read_secs=0.1, inside=[] if temp is None
                         else backend.bears(
                             FakeExecutable("jit_" + fn, FakeStats(temp))))
    d = json.loads(json.dumps(ledger.as_dict()))
    assert probe.program_memory_rows(d) == [
        ("decode", "bucket=64", "hit", 3.5, 3.50000002),
        ("prefill", "bucket=512", "hit", 2.0, 2.00000002)]
    assert probe.program_memory_rows(d, top=1)[0][0] == "decode"
    assert probe.program_memory_rows({}) == []
    assert probe.program_memory_rows({"programs": {"f": {"n_compile": 1}}}
                                     ) == []  # a ledger without the records


# ---------------------------------------------------------------------------
# MemWatch: HBM gauges, watermarks, degradation
# ---------------------------------------------------------------------------


def make_memwatch(devices, **kw):
    reg = telemetry.TelemetryRegistry()
    t = {"now": 0.0}
    m = mw.MemWatch(reg, devices_fn=lambda: devices,
                    clock=lambda: t["now"], **kw)
    return m, reg, t


def test_memwatch_exports_per_device_gauges_rate_limited():
    d0 = FakeDevice({"bytes_in_use": 100.0, "peak_bytes_in_use": 150.0,
                     "bytes_limit": 1000.0})
    d1 = FakeDevice({"bytes_in_use": 300.0, "peak_bytes_in_use": 300.0,
                     "bytes_limit": 1000.0})
    m, reg, t = make_memwatch([d0, d1], sample_interval_secs=10.0)
    assert m.sample() == 300.0
    gauges = reg.snapshot(reset=False)["gauges"]
    assert gauges["hbm/bytes_in_use{device=0}"] == 100.0
    assert gauges["hbm/peak_bytes{device=0}"] == 150.0
    assert gauges["hbm/limit_bytes{device=1}"] == 1000.0
    assert gauges["hbm/bytes_in_use{device=1}"] == 300.0
    # inside the interval: rate-limited (None), gauges untouched
    d0.stats["bytes_in_use"] = 900.0
    t["now"] = 5.0
    assert m.sample() is None
    assert reg.snapshot(reset=False)["gauges"][
        "hbm/bytes_in_use{device=0}"] == 100.0
    # force bypasses the limiter; peak_gb tracks the high-water mark
    assert m.sample(force=True) == 900.0
    assert m.peak_gb() == 900.0 / (1 << 30)
    # past the interval the limiter opens again
    t["now"] = 16.0
    assert m.sample() == 900.0


def test_memwatch_watermark_sites_are_monotonic_maxima():
    dev = FakeDevice({"bytes_in_use": 100.0, "peak_bytes_in_use": 100.0,
                      "bytes_limit": 1000.0})
    m, reg, t = make_memwatch([dev])
    with m.watermark("weight_stream/gather"):
        dev.stats["bytes_in_use"] = 800.0
    gauges = reg.snapshot(reset=False)["gauges"]
    assert gauges["hbm/watermark_bytes{site=weight_stream/gather}"] == 800.0
    # a later, smaller peak must not lower the recorded high-water mark
    dev.stats["bytes_in_use"] = 200.0
    with m.watermark("weight_stream/gather"):
        pass
    assert reg.snapshot(reset=False)["gauges"][
        "hbm/watermark_bytes{site=weight_stream/gather}"] == 800.0
    assert m.site_peaks() == {"weight_stream/gather": 800.0}


def test_memwatch_degrades_once_without_memory_stats(monkeypatch):
    """CPU-backend contract: one warning + one counter bump, then quiet —
    never fake zero gauges that read as an empty chip."""
    warned = []
    monkeypatch.setattr(mw.logger, "warning", warned.append)

    class CpuDevice:  # no memory_stats attribute at all
        pass

    m, reg, t = make_memwatch([CpuDevice()])
    assert m.sample(force=True) is None
    assert m.sample(force=True) is None
    assert m.sample(force=True) is None
    snap = reg.snapshot(reset=False)
    assert snap["counters"]["hbm/memory_stats_unavailable"] == 1.0
    assert not any(k.startswith("hbm/bytes") for k in snap["gauges"])
    assert len(warned) == 1 and "degraded" in warned[0]
    # degraded watermarks are cheap no-ops, not errors
    with m.watermark("train/fwd_bwd"):
        pass
    assert m.site_peaks() == {}
    assert m.peak_gb() == 0.0


def test_memwatch_skips_devices_that_return_no_stats():
    """Mixed fleets: a device returning None/{} (some runtime versions)
    is skipped while real readings still export."""

    class NoneDevice:
        def memory_stats(self):
            return None

    dev = FakeDevice({"bytes_in_use": 42.0, "bytes_limit": 100.0})
    m, reg, t = make_memwatch([NoneDevice(), dev])
    assert m.sample(force=True) == 42.0
    gauges = reg.snapshot(reset=False)["gauges"]
    # the real device is index 0 of the READINGS, not of the device list
    assert gauges["hbm/bytes_in_use{device=0}"] == 42.0


# ---------------------------------------------------------------------------
# disabled contract: bit-identical scrape
# ---------------------------------------------------------------------------


def test_disabled_keeps_null_sinks_and_scrape_bit_identical():
    reg = telemetry.TelemetryRegistry()
    reg.inc("train/optimizer_steps")
    reg.set_gauge("train/mfu", 0.41)
    before = telemetry.render_prometheus(reg.snapshot(reset=False))

    assert cw.configure(CompileWatchConfig(enabled=False), reg) is cw.NULL
    assert mw.configure(CompileWatchConfig(enabled=False), reg) is mw.NULL
    try:
        assert not cw.enabled() and not mw.enabled()

        def fn(x):
            return x

        # the raw function object comes back — zero per-call overhead
        assert cw.watched_jit("train/grad", fn) is fn
        assert not cw.inflight()
        assert mw.sample(force=True) is None
        with mw.watermark("train/fwd_bwd"):
            pass
        assert mw.peak_gb() == 0.0
    finally:
        cw.shutdown()
        mw.shutdown()
    after = telemetry.render_prometheus(reg.snapshot(reset=False))
    assert after == before
    assert "compile" not in after and "hbm" not in after


def test_configure_enabled_installs_and_shutdown_restores_null():
    reg = telemetry.TelemetryRegistry()
    try:
        watch = cw.configure(
            CompileWatchConfig(enabled=True, storm_warmup_calls=3), reg,
        )
        assert watch is cw.get() and cw.enabled()
        assert watch.storm_warmup_calls == 3
        m = mw.configure(
            CompileWatchConfig(enabled=True, mem_sample_interval_secs=2.0),
            reg, devices_fn=lambda: [],
        )
        assert m is mw.get() and mw.enabled()
        assert m.sample_interval_secs == 2.0
        wrapped = cw.watched_jit("train/grad", lambda x: x)
        assert wrapped.__wrapped__ is not None
        wrapped(Arr((2, 2)))
        assert reg.snapshot(reset=False)["counters"][
            "compile/events{fn=train/grad}"] == 1.0
    finally:
        cw.shutdown()
        mw.shutdown()
    assert cw.get() is cw.NULL and mw.get() is mw.NULL


# ---------------------------------------------------------------------------
# aggregator: derived utilization + fleet rollups
# ---------------------------------------------------------------------------


def test_derive_hbm_utilization_injects_ratio_per_device():
    payload = {"gauges": {
        "hbm/bytes_in_use{device=0}": 750.0,
        "hbm/limit_bytes{device=0}": 1000.0,
        "hbm/bytes_in_use{device=1}": 100.0,
        "hbm/limit_bytes{device=1}": 400.0,
        "train/mfu": 0.4,
    }, "counters": {}}
    telemetry.TelemetryAggregator._derive_hbm_utilization(payload)
    assert payload["gauges"]["hbm/utilization{device=0}"] == 0.75
    assert payload["gauges"]["hbm/utilization{device=1}"] == 0.25


def test_derive_hbm_utilization_no_hbm_gauges_no_mutation():
    """The merged-scrape bit-identity hinges on the derivation being a
    strict no-op when the observatory exports nothing."""
    payload = {"gauges": {"train/mfu": 0.4, "master/step_secs": 3.0},
               "counters": {"train/optimizer_steps": 12.0}}
    before = json.dumps(payload, sort_keys=True)
    telemetry.TelemetryAggregator._derive_hbm_utilization(payload)
    assert json.dumps(payload, sort_keys=True) == before
    # bytes_in_use without a limit (device never reported one): no ratio
    payload = {"gauges": {"hbm/bytes_in_use{device=0}": 750.0}}
    telemetry.TelemetryAggregator._derive_hbm_utilization(payload)
    assert "hbm/utilization{device=0}" not in payload["gauges"]


# ---------------------------------------------------------------------------
# sentinel: the compile/HBM rule pack
# ---------------------------------------------------------------------------


def test_compile_rule_pack_armed_only_with_the_observatory():
    base = {r.id for r in rules_from_config(SentinelConfig(enabled=True))}
    armed = {r.id for r in rules_from_config(
        SentinelConfig(enabled=True), compile_watch_enabled=True)}
    pack = {r["id"] for r in COMPILE_RULES}
    assert pack == {"recompile_storm", "hbm_pressure", "compile_stall"}
    assert pack & base == set()
    assert pack <= armed
    assert armed - pack == base == {r["id"] for r in DEFAULT_RULES}
    # the pack parses clean: severities, metrics, durations all validated
    by_id = {r.id: r for r in rules_from_config(
        SentinelConfig(enabled=True), compile_watch_enabled=True)}
    assert by_id["recompile_storm"].kind == "rate"
    assert by_id["hbm_pressure"].metric == "hbm/utilization"
    assert by_id["compile_stall"].severity == "critical"


def test_trainer_stalled_carries_compile_unless_guard():
    rules = {r.id: r for r in
             rules_from_config(SentinelConfig(enabled=True))}
    stalled = rules["trainer_stalled"]
    assert stalled.unless_metric == "compile/inflight"
    # the drive-by: a wedged trainer alerts in minutes, not after the old
    # blanket 30-minute grace
    assert stalled.for_secs == 300.0


def test_unless_grammar_is_validated():
    absence = {"id": "r", "metric": "train/optimizer_steps",
               "kind": "absence", "for": 60, "cooldown": 60}
    # valid: absence rule + catalog metric
    [r] = parse_rules([dict(absence, unless="compile/inflight")])
    assert r.unless_metric == "compile/inflight"
    # unless on a non-absence rule is a config error
    with pytest.raises(SentinelConfigError, match="absence"):
        parse_rules([{"id": "r", "metric": "train/approx_kl",
                      "kind": "threshold", "op": "gt", "value": 1.0,
                      "unless": "compile/inflight"}])
    # unknown unless metric is caught with a did-you-mean hint
    with pytest.raises(SentinelConfigError, match="unless"):
        parse_rules([dict(absence, unless="compile/inflite")])


# ---------------------------------------------------------------------------
# config validation (api/cli_args.py)
# ---------------------------------------------------------------------------


def test_validate_config_gates_the_observatory():
    from areal_tpu.api import cli_args
    from areal_tpu.experiments.ppo_math_exp import PPOMATHConfig

    cfg = PPOMATHConfig()
    cfg.compile_watch.enabled = True
    with pytest.raises(cli_args.ConfigError, match="telemetry"):
        cli_args.validate_config(cfg)
    cfg.telemetry.enabled = True
    cli_args.validate_config(cfg)
    cfg.compile_watch.storm_warmup_calls = 0
    with pytest.raises(cli_args.ConfigError, match="storm_warmup"):
        cli_args.validate_config(cfg)
    cfg.compile_watch.storm_warmup_calls = 16
    cfg.compile_watch.mem_sample_interval_secs = -1.0
    with pytest.raises(cli_args.ConfigError, match="mem_sample"):
        cli_args.validate_config(cfg)


def test_validate_config_cross_checks_shape_budgets(monkeypatch):
    """Unified compiled-shape accounting: serving.max_compiled_shapes
    must cover the trainer fill sweep's worst-case candidate count too,
    not only the serving policy's own decode/prefill grids."""
    from areal_tpu.api import cli_args
    from areal_tpu.backend import microbatch
    from areal_tpu.experiments.ppo_math_exp import PPOMATHConfig

    cands = microbatch.worst_case_row_candidates()
    assert cands >= 1
    cfg = PPOMATHConfig()
    cfg.telemetry.enabled = True
    cfg.compile_watch.enabled = True
    cfg.serving.enabled = True
    # generous enough for the serving policy's own worst case AND the
    # trainer sweep: everything validates
    cfg.serving.max_compiled_shapes = 4096
    cli_args.validate_config(cfg)
    # a trainer sweep that outgrows the serving budget is caught at
    # parse time with the sweep's own number in the message
    monkeypatch.setattr(microbatch, "worst_case_row_candidates",
                        lambda: 5000)
    with pytest.raises(cli_args.ConfigError,
                       match="worst-case candidate count"):
        cli_args.validate_config(cfg)
