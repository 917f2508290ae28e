"""The ``setup_*`` readers (``benchmark/setup_ledger.py``) over a small
recorded ``records`` and over what the program's own compile ledger
writes."""

import json
import os

import pytest

from benchmark import harness, setup_ledger

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
METRICS = ["setup_trace_lower_s", "setup_compile_s", "setup_cache_misses",
           "setup_programs", "setup_slowest_program_s",
           "setup_unattributed_s"]


@pytest.fixture
def records():
    with open(os.path.join(DATA, "setup_records.json")) as f:
        return json.load(f)


def read_all(records):
    return {m: harness.metric_reader(m)(records) for m in METRICS}


def test_the_six_values_by_hand(records):
    # busy 12.5 = trace 4.5 + lower 2.25 + compile 6.0 less 0.25 s of a
    # helper traced while lowering; the stopwatch is 0.5 + 5.0 + 14.5
    assert read_all(records) == {
        "setup_trace_lower_s": 6.5,       # 12.5 - 6.0
        "setup_compile_s": 6.0,
        "setup_cache_misses": 1,
        "setup_programs": 4,              # 2 + 1 + 1 compile spans
        "setup_slowest_program_s": 9.5,   # train_grad_sliced's one compile
        "setup_unattributed_s": 7.5,      # 20.0 - 12.5
    }


def test_the_three_parts_add_up_to_the_stopwatch(records):
    v = read_all(records)
    parts = (v["setup_trace_lower_s"] + v["setup_compile_s"]
             + v["setup_unattributed_s"])
    assert parts == setup_ledger.stopwatch_s(records) == 20.0
    # the run's setup_s also holds the moments between the blocks
    assert 0 <= records["setup_s"] - parts < 1.0


def test_a_program_without_the_ledger_reads_none(records):
    led = records["setup_split"]["compile_cache_after_warmup"]
    parent = {k: led[k] for k in ("dir", "hits", "misses", "trace_secs",
                                  "lower_secs", "compile_secs",
                                  "cache_read_secs")}
    records["setup_split"]["compile_cache_after_warmup"] = parent
    assert set(read_all(records).values()) == {None}
    assert set(read_all({}).values()) == {None}
    assert set(read_all({"setup_split": {}}).values()) == {None}


def test_the_ring_is_never_read(records):
    before = read_all(records)
    records["setup_split"]["compile_cache_after_warmup"]["spans"] = []
    assert read_all(records) == before


def test_overlapping_spans_are_reckoned_as_a_union():
    """Through the program's own ledger: a callee traced inside its
    caller's trace, a helper traced while lowering and a second thread
    compiling meanwhile count once where they overlap."""
    import threading

    from areal_tpu.base import compile_watch as cw

    if not hasattr(cw.CacheStats, "_on_span"):
        pytest.skip("this program has no compile ledger")
    trace = "/jax/core/compile/jaxpr_trace_duration"
    lower = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    comp = "/jax/core/compile/backend_compile_duration"
    ledger = cw.CacheStats()

    def stage(event, fn, t0, t1, inside=()):
        ledger._on_enter(event, t0, fun_name=fn)
        for f in inside:
            f()
        ledger._on_span(event, t0, t1, fun_name=fn)

    stage(trace, "train_apply", 0.0, 4.0,
          [lambda: stage(trace, "multiply", 1.0, 2.0),
           lambda: stage(trace, "add", 2.5, 3.0)])
    stage(lower, "jit(train_apply)", 4.0, 6.0,
          [lambda: stage(trace, "helper", 4.5, 5.0)])
    stage(comp, "jit(train_apply)", 6.0, 9.0,
          [lambda: ledger._on_event(
              "/jax/compilation_cache/cache_misses")])
    th = threading.Thread(target=stage, args=(comp, "jit(decode)", 7.0, 8.0))
    th.start()
    th.join(10)
    assert not th.is_alive()
    records = {"setup_split": {
        "imports_s": 1.0, "weights_backend_s": 2.0, "warmup_s": 12.0,
        "compile_cache_after_warmup": json.loads(
            json.dumps(ledger.as_dict()))}}
    assert read_all(records) == {
        "setup_trace_lower_s": 6.0,      # [0, 6): not 4 + 1 + 0.5 + 2 + 0.5
        "setup_compile_s": 4.0,          # per thread, summed
        "setup_cache_misses": 1,
        "setup_programs": 2,
        "setup_slowest_program_s": 9.0,
        "setup_unattributed_s": 5.0,     # 15 - 10
    }
