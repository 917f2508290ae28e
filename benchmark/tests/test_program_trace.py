"""``program_trace``: the name stripping, the program / scope / idle
attribution over plain planes with known numbers, the metric readers on
top of it, and the whole reduction of a small trace recorded on the chip
(``record_scoped_trace.py``) against the numbers pinned beside it."""

import json
import os
import shutil

import pytest

from benchmark import harness
from benchmark import program_trace as pt

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.mark.parametrize("framework_name, scope", [
    ("jit(train_grad)/transpose(jvp(mlp))/dot_general", "mlp"),
    ("jit(train_grad)/jvp(mlp)/dot_general:", "mlp"),
    ("jit(infer_forward)/layer_scan/while/body/checkpoint/attention/"
     "pallas_flash_attention/jit(flash)/custom_call", "attention"),
    ("jit(train_grad)/transpose(jvp(layer_scan))/while/body/"
     "dynamic_update_slice", "layer_scan"),
    ("jit(train_grad)/transpose(jvp(layer_scan))/while/body/closed_call/"
     "checkpoint/rematted_computation/mlp_norm/mul", "mlp_norm"),
    ("jit(train_grad)/jvp(xent)/jit(_take)/gather", "xent"),
    ("jit(train_grad)/jvp(head)/dot_general;jit(train_grad)/jvp(xent)/exp",
     "head"),
    ("jit(train_apply)/adam/mul", "adam"),
    ("jit(train_grad)/transpose", None),   # a primitive, not a wrapper
    ("jit(headroom)/add", None),           # whole components only
    ("", None),
])
def test_scope_of(framework_name, scope):
    assert pt.scope_of(framework_name) == scope


def test_program_of_and_op_name():
    assert pt.program_of("jit_train_grad(11543105221590201639)") == (
        "train_grad", "11543105221590201639")
    assert pt.program_of("odd") == ("odd", "")
    assert pt.op_name("%fusion.12 = bf16[8,128]{1,0} fusion(...)") == \
        "fusion.12"
    assert pt.is_flash("flash_attention.6")
    assert pt.is_flash("flash_mha_bwd_dkv.3")
    assert not pt.is_flash("fusion.6")


def planes():
    # One chip. Program A = infer_forward(1) over [1, 4]: a `while` [1, 3]
    # holding fusion.1 [1, 1.5] (mlp) and fusion.2 [2, 2.75] (no name),
    # then a flash call [3, 4]. Program B = train_grad(2) over [6, 9]:
    # fusion.1 [6, 8] (head: same instruction name, another program) and
    # copy.1 [8, 9] (grad_accum). Idle: [4, 6). Window [1, 9].
    ops = [(1.0, 3.0, "while.1"), (1.0, 1.5, "fusion.1"),
           (2.0, 2.75, "fusion.2"), (3.0, 4.0, "flash_attention.3"),
           (6.0, 8.0, "fusion.1"), (8.0, 9.0, "copy.1")]
    modules = [(1.0, 4.0, "jit_infer_forward(1)"),
               (6.0, 9.0, "jit_train_grad(2)")]
    host = [(0.5, 5.0, "areal/ppo/inference"),
            (3.5, 4.5, "areal/infer/fetch"),
            (5.0, 9.5, "bench/not-ours"),
            (5.5, 5.75, "areal/train/upload")]
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": modules},
            {"name": "Async XLA Ops", "events": [(0.0, 20.0, "x")]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": host}]},
    ]


NAMES = {
    ("1", "while.1"): "jit(infer_forward)/layer_scan/while",
    ("1", "fusion.1"): "jit(infer_forward)/layer_scan/while/body/mlp/dot",
    ("1", "fusion.2"): "jit(infer_forward)/while/body/mul",
    ("1", "flash_attention.3"): "jit(infer_forward)/attention/custom_call",
    ("2", "fusion.1"): "jit(train_grad)/transpose(jvp(head))/dot_general",
    ("2", "copy.1"): "jit(train_grad)/grad_accum/add",
}


def test_programs_scopes_and_idle_over_plain_data():
    red = pt.reduce_planes(planes(), NAMES)
    assert red["window_s"] == pytest.approx(8.0)
    assert red["busy_s"] == pytest.approx(6.0)
    assert red["programs"] == pytest.approx(
        {"infer_forward": 3.0, "train_grad": 3.0})
    assert red["scopes"] == pytest.approx({
        "layer_scan": 0.75,   # the while's self time
        "mlp": 0.5, "unscoped": 0.75, "flash": 1.0,
        "head": 2.0, "grad_accum": 1.0})
    # every busy second is in exactly one bucket
    assert sum(red["scopes"].values()) == pytest.approx(red["busy_s"])
    assert red["scope_ops"]["head"] == pytest.approx({"fusion": 2.0})
    # one gap [4, 6), middle 5.0: infer/fetch ended at 4.5, ppo/inference
    # at 5.0 (exclusive) → under no areal/ span
    assert red["idle"] == pytest.approx({"unspanned": 2.0})
    assert red["idle_s"] == pytest.approx(2.0)


def test_idle_goes_to_the_innermost_span():
    pl = planes()
    pl[1]["lines"][0]["events"] = [
        (0.5, 7.0, "areal/ppo/inference"), (4.5, 5.5, "areal/infer/fetch")]
    red = pt.reduce_planes(pl, NAMES)
    assert red["idle"] == pytest.approx({"areal/infer/fetch": 2.0})


def test_a_program_without_the_names_reads_as_nothing():
    """The parent of the PR that added the names: modules called jit_f,
    no scope on any op, no areal/ span."""
    pl = planes()
    pl[0]["lines"][1]["events"] = [(1.0, 4.0, "jit_f(1)"),
                                   (6.0, 9.0, "jit_f(2)")]
    pl[1]["lines"][0]["events"] = [(5.0, 9.5, "bench/not-ours")]
    names = {k: "jit(f)/while/body/mul" for k in NAMES}
    red = pt.reduce_planes(pl, names)
    assert red["programs"] == pytest.approx({"f": 6.0})
    assert red["scopes"] is None and red["idle"] is None
    # and with no table at all (xprof missing)
    assert pt.reduce_planes(planes(), None)["scopes"] is None
    assert pt.reduce_planes([], NAMES) == {}


def test_span_counts_sum_numeric_attributes():
    counts = pt.span_counts([
        ("areal/train/upload", {"real_tokens": 90, "padded_tokens": 128,
                                "n_mbs": 4, "grid": "2x16"}),
        ("areal/train/upload", {"real_tokens": 100, "padded_tokens": 128,
                                "n_mbs": 4, "grid": "2x16"}),
        ("areal/infer/fetch", {}),
    ])
    assert counts["areal/train/upload"] == {
        "n": 2, "real_tokens": 190, "padded_tokens": 256, "n_mbs": 8}
    assert counts["areal/infer/fetch"] == {"n": 1}


@pytest.fixture()
def loaded(monkeypatch):
    """The readers over the plain-data reduction, as if the newest trace
    file had held it."""
    red = pt.reduce_planes(planes(), NAMES)
    red["counts"] = pt.span_counts([
        ("areal/train/upload", {"real_tokens": 90, "padded_tokens": 120}),
        ("areal/infer/upload", {"real_tokens": 30, "padded_tokens": 40}),
        ("areal/infer/upload", {"real_tokens": 30, "padded_tokens": 60})])
    monkeypatch.setattr(pt, "newest_trace", lambda: "fake")
    monkeypatch.setitem(pt._LOADED, "fake", red)
    return {"trace": {"busy_s": 6.0}}


@pytest.mark.parametrize("metric, value", [
    ("infer_pass_busy_pct", 50.0),
    ("optimizer_busy_pct", None),          # no train_apply in the trace
    ("mlp_busy_pct", 100 * 0.5 / 6),
    ("head_xent_busy_pct", 100 * 2.0 / 6),
    ("attn_proj_busy_pct", 0.0),
    ("attn_glue_busy_pct", 0.0),           # the flash op is not glue
    ("param_cast_busy_pct", 0.0),
    ("grad_accum_busy_pct", 100 * 1.0 / 6),
    ("unscoped_busy_pct", 100 * 0.75 / 6),
    ("fetch_idle_pct", 0.0),
    ("upload_idle_pct", 0.0),
    ("pack_fill_span_pct", 75.0),
    ("infer_pack_fill_span_pct", 60.0),
    ("unspanned_idle_pct", 100.0),
])
def test_metric_readers(loaded, metric, value):
    got = harness.metric_reader(metric)(loaded)
    if value is None:
        assert got is None
    else:
        assert got == pytest.approx(value)
    # an untraced run, or one whose program has none of the names
    assert harness.metric_reader(metric)({"trace": {}}) is None


def test_scope_list_is_the_programs():
    from areal_tpu.base import telemetry

    assert set(pt.SCOPES) == set(telemetry.DEVICE_SCOPES)
    assert pt.SPAN_PREFIX == telemetry.ANNOTATION_PREFIX


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """xprof writes an ``.op_stats.pb`` beside the file it reads: read a
    copy."""
    src = os.path.join(HERE, "data", "scoped.xplane.pb")
    if not os.path.isfile(src):
        pytest.skip("no recorded trace")
    dst = tmp_path_factory.mktemp("scoped") / "scoped.xplane.pb"
    shutil.copy(src, dst)
    red = pt.reduce_file(str(dst))
    red.pop("path")
    return red


def test_recorded_trace_matches_its_pinned_numbers(recorded):
    with open(os.path.join(HERE, "data", "scoped.expected.json")) as f:
        want = json.load(f)
    assert set(recorded) == set(want)
    for key in ("window_s", "busy_s", "idle_s"):
        assert recorded[key] == pytest.approx(want[key], rel=1e-9)
    for key in ("programs", "scopes", "idle"):
        assert recorded[key] == pytest.approx(want[key], rel=1e-9)
    assert recorded["counts"] == want["counts"]


def test_recorded_trace_reads_as_the_recorder_wrote_it(recorded):
    assert set(recorded["programs"]) == {"infer_forward", "train_grad"}
    scopes = recorded["scopes"]
    # the matmuls under mlp (forward, remat and backward) and head carry
    # the time; names survive checkpoint, scan and the transpose
    assert {"mlp", "head", "layer_scan"} <= set(scopes)
    assert scopes["mlp"] + scopes["head"] > 0.5 * recorded["busy_s"]
    assert sum(scopes.values()) == pytest.approx(recorded["busy_s"])
    assert scopes.get("unscoped", 0.0) < 0.1 * recorded["busy_s"]
    # spans: three inference and three train steps, counts as recorded
    c = recorded["counts"]
    assert c["areal/infer/upload"]["n"] == 3
    assert c["areal/infer/upload"]["real_tokens"] == 900 + 901 + 902
    assert c["areal/train/upload"]["padded_tokens"] == 3 * 4096
    idle = recorded["idle"]
    # a whole gap goes to the span over its middle: the device finishes a
    # call in ~0.1 ms, so it idles while the host waits for the result,
    # sleeps under no span and uploads again
    assert set(idle) <= {"unspanned"} | set(c)
    assert idle["areal/train/fetch_stats"] > 0.003
    assert idle["unspanned"] > 0.009  # three sleeps of 3 ms
    assert sum(idle.values()) == pytest.approx(recorded["idle_s"])
    assert recorded["idle_s"] + recorded["busy_s"] == pytest.approx(
        recorded["window_s"])
