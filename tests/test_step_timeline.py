"""The trainer's step on the profiler's timeline: ``telemetry.span`` opens
an ``areal/<name>`` annotation in any ``jax.profiler`` capture, registry on
or off; the spans of one ``inference`` + ``train_step`` nest as the code
nests and carry the packer's counts; tracing adds no host sync; every
jitted program of the train path has a name of its own and carries the
``jax.named_scope`` names of ``telemetry.DEVICE_SCOPES``; the weights are
cast where they are written (``train_apply``) and in no program that
reads them."""

import collections
import dataclasses
import glob
import math
import os
import re

import pytest

import jax

from areal_tpu.algorithms.ppo import PPOActorInterface, PPOHyperparameters
from areal_tpu.api.data import MicroBatchSpec
from areal_tpu.api.model import FinetuneSpec, Model
from areal_tpu.api.train_config import TelemetryConfig
from areal_tpu.backend import jax_train
from areal_tpu.backend import microbatch as mbu
from areal_tpu.base import telemetry
from areal_tpu.models import transformer
from areal_tpu.models.config import tiny_config

from test_uniform_prep import _engine, _make_batch

SPEC = MicroBatchSpec(max_tokens_per_mb=64)
HP = dict(ppo_n_minibatches=2, adv_norm=True, kl_ctl=0.0, disable_value=True)
PREFIX = telemetry.ANNOTATION_PREFIX

# child span -> the span it must lie inside
NESTING = {
    "infer/split_pack": "ppo/inference",
    "infer/upload": "ppo/inference",
    "infer/dispatch": "ppo/inference",
    "infer/fetch": "ppo/inference",
    "infer/scatter_back": "ppo/inference",
    "train/split_pack": "ppo/train_step",
    "train/upload": "ppo/train_step",
    "train/adv_prep": "ppo/train_step",
    "train/fwd_bwd": "ppo/train_step",
    "train/optimizer": "ppo/train_step",
    "train/apply_dispatch": "train/optimizer",
    "train/fetch_stats": "train/optimizer",
    "train/finish_stats": "train/optimizer",
}


def _step(model, iface, batch):
    batch.update_(iface.inference(model, batch, SPEC))
    return iface.train_step(model, batch, SPEC)


def _capture(tmp_path, fn):
    """Run ``fn`` under a profiler capture with the benchmark's options;
    returns [(name, start_ns, end_ns, stats)] of the ``areal/`` events."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    out = []
    for pl in jax.profiler.ProfileData.from_file(path).planes:
        for ln in pl.lines:
            for ev in ln.events:
                if ev.name.startswith(PREFIX):
                    out.append((ev.name[len(PREFIX):], ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


@pytest.fixture()
def registry():
    tel = telemetry.configure("timeline", "t", "trainer",
                              cfg=TelemetryConfig(enabled=True), push=False)
    yield tel.registry
    telemetry.shutdown()


@pytest.mark.parametrize("group_adv_norm", [False, True])
@pytest.mark.parametrize("registry_on", [False, True])
def test_spans_of_one_step_in_a_capture(tmp_path, registry_on,
                                        group_adv_norm, request):
    """Group normalization keeps the advantage prep on the host and takes
    a ``train_batch`` per PPO minibatch: the same tree but for
    ``train/adv_prep``, with a pack and an upload per minibatch."""
    reg = request.getfixturevalue("registry") if registry_on else None
    assert telemetry.enabled() is registry_on
    hp = PPOHyperparameters(**HP, group_adv_norm=group_adv_norm)
    model, iface = _engine(), PPOActorInterface(hp)
    batch = _make_batch()
    _step(model, iface, batch)  # compile outside the capture
    if reg is not None:
        reg.snapshot(reset=True)
    events = _capture(tmp_path, lambda: _step(model, iface, batch))
    by_name = {}
    for name, s, e, stats in events:
        by_name.setdefault(name, []).append((s, e, stats))
    nesting = dict(NESTING)
    if group_adv_norm:
        del nesting["train/adv_prep"]
        assert "train/adv_prep" not in by_name
    assert set(nesting) | {"ppo/inference", "ppo/train_step"} <= set(by_name)
    for child, parent in nesting.items():
        for s, e, _ in by_name[child]:
            assert any(ps <= s and e <= pe for ps, pe, _ in by_name[parent]), \
                f"{child} is not inside {parent}"
    # one upload / dispatch / fetch per inference micro-batch, one
    # optimizer tree per PPO minibatch
    n_inf = by_name["infer/upload"][0][2]["n_mbs"]
    for k in ("infer/upload", "infer/dispatch", "infer/fetch"):
        assert len(by_name[k]) == n_inf
    for k in ("train/fwd_bwd", "train/apply_dispatch", "train/fetch_stats",
              "train/finish_stats"):
        assert len(by_name[k]) == HP["ppo_n_minibatches"]
    # the whole batch goes up before its first grad program is dispatched
    ups, loops = by_name["train/upload"], by_name["train/fwd_bwd"]
    assert len(ups) == (HP["ppo_n_minibatches"] if group_adv_norm else 1)
    for (_, up_end, _), (loop_start, _, _) in zip(ups, loops):
        assert up_end <= loop_start
    root = by_name["ppo/train_step"][0][2]
    assert root["sequences"] == batch.bs
    assert root["real_tokens"] == sum(batch.total_lens("packed_input_ids"))
    assert sum(u[2]["real_tokens"] for u in ups) == root["real_tokens"]
    if reg is not None:
        # the registry records the same spans, with the same attributes
        spans = reg.snapshot(reset=True)["spans"]
        assert {s["name"] for s in spans} >= set(nesting)
        recorded = [s for s in spans if s["name"] == "train/upload"]
        assert [s["attrs"] for s in recorded] == [u[2] for u in ups]


def test_upload_span_counts_are_the_packers(tmp_path):
    model, iface = _engine(), PPOActorInterface(PPOHyperparameters(**HP))
    eng, batch = model.module, _make_batch()
    _step(model, iface, batch)
    events = _capture(tmp_path, lambda: _step(model, iface, batch))
    # the packer's own numbers for the same sample and specs
    def pack(spec):
        return mbu.split_into_microbatches(
            batch, spec, length_bucket=eng.length_bucket,
            rows_bucket=eng.rows_bucket, seqs_bucket=eng.seqs_bucket,
            fill_bucket=eng.fill_bucket)

    train = pack(dataclasses.replace(SPEC, n_mbs=HP["ppo_n_minibatches"]))
    infer = pack(SPEC)
    (up,) = [st for n, _, _, st in events if n == "train/upload"]
    R, L = train[0].layout.shape
    assert up == {"real_tokens": sum(mb.n_tokens for mb in train),
                  "padded_tokens": len(train) * R * L,
                  "n_mbs": len(train), "grid": f"{R}x{L}"}
    ups = [st for n, _, _, st in events if n == "infer/upload"]
    R, L = infer[0].layout.shape
    assert [u["real_tokens"] for u in ups] == [mb.n_tokens for mb in infer]
    assert all(u["padded_tokens"] == R * L and u["grid"] == f"{R}x{L}"
               and u["n_mbs"] == len(infer) for u in ups)
    assert sum(u["real_tokens"] for u in ups) == up["real_tokens"]


def _count_fetches(monkeypatch, fn):
    """Blocking device→host reads during ``fn``: ``jax.device_get``,
    ``jax.block_until_ready`` and an array's own host conversions."""
    from jax._src import array as jarray

    n = {"device_get": 0, "block": 0, "value": 0}

    def counted(key, orig):
        def wrapper(*a, **kw):
            n[key] += 1
            return orig(*a, **kw)
        return wrapper

    monkeypatch.setattr(jax, "device_get",
                        counted("device_get", jax.device_get))
    monkeypatch.setattr(jax, "block_until_ready",
                        counted("block", jax.block_until_ready))
    orig_value = jarray.ArrayImpl._value
    monkeypatch.setattr(
        jarray.ArrayImpl, "_value",
        property(counted("value", orig_value.fget)))
    fn()
    monkeypatch.undo()
    return n


def test_telemetry_adds_no_host_sync_to_a_step(monkeypatch, registry):
    hp = PPOHyperparameters(**HP)
    counts = {}
    for on in (True, False):
        if not on:
            telemetry.shutdown()
        assert telemetry.enabled() is on
        model, iface, batch = _engine(), PPOActorInterface(hp), _make_batch()
        _step(model, iface, batch)
        counts[on] = _count_fetches(
            monkeypatch, lambda: _step(model, iface, batch))
    assert counts[True] == counts[False]
    assert counts[True]["block"] == 0
    # one device_get a PPO minibatch: the step's one blocking fetch
    assert counts[True]["device_get"] == HP["ppo_n_minibatches"]


# ---- names on the device ----

@pytest.fixture(scope="module")
def recorded():
    """For every program one inference + train_step of the tiny model
    builds (advantages on the device and, with group normalization, on
    the host), by HloModule name: the set of framework op names, and call
    by call a count of the (dtype, shape) of the arrays it was given."""
    seen, inputs = {}, {}
    orig = jax.jit

    def recording_jit(fn, *a, **kw):
        jitted = orig(fn, *a, **kw)

        def call(*args, **kwargs):
            if fn.__name__ in telemetry.DEVICE_PROGRAMS or \
                    fn.__name__ in ("f", "<lambda>", "init"):
                text = jitted.lower(*args, **kwargs).compile().as_text()
                name = re.search(r"HloModule (\S+?),", text).group(1)
                seen.setdefault(name, set()).update(
                    re.findall(r'op_name="([^"]*)"', text))
                inputs.setdefault(name, []).append(
                    collections.Counter(
                        (str(x.dtype), x.shape)
                        for x in jax.tree.leaves((args, kwargs))))
            return jitted(*args, **kwargs)

        return call

    def bf16_engine():
        # as the trainer runs: f32 masters, bf16 compute
        cfg = tiny_config(vocab_size=128)
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        backend = jax_train.JaxTrainBackend(
            optimizer=jax_train.OptimizerConfig(
                lr=1e-3, lr_scheduler_type="constant"),
            compute_dtype="bfloat16", length_bucket=16, rows_bucket=2,
            seqs_bucket=4, remat=True)
        return backend.initialize(Model("actor", (cfg, params)),
                                  FinetuneSpec(1, 8, 4))

    mp = pytest.MonkeyPatch()
    mp.setattr(jax_train.jax, "jit", recording_jit)
    try:
        for extra in ({}, {"group_adv_norm": True}):
            hp = PPOHyperparameters(**{**HP, **extra})
            model, iface = bf16_engine(), PPOActorInterface(hp)
            _step(model, iface, _make_batch())
    finally:
        mp.undo()
    return seen, inputs


@pytest.fixture(scope="module")
def programs(recorded):
    return recorded[0]


@pytest.fixture(scope="module")
def program_inputs(recorded):
    return recorded[1]


def _scopes_in(op_names):
    found = set()
    for name in op_names:
        for part in name.split("/"):
            inner = re.sub(r"^(?:\w+\()+|\)+$", "", part)
            if inner in telemetry.DEVICE_SCOPES:
                found.add(inner)
    return found


BLOCK = {"attn_norm", "qkv_proj", "rope", "attention", "o_proj", "mlp_norm",
         "mlp", "layer_scan"}
FORWARD = BLOCK | {"embed", "final_norm", "head", "xent"}
EXPECTED_SCOPES = {
    "jit_infer_forward": FORWARD,
    "jit_train_grad_sliced": FORWARD | {"ppo_loss", "grad_accum"},
    "jit_train_apply": {"grad_clip", "adam", "param_update"},
    "jit_adv_prep": {"gae"},
    "jit_opt_init": set(),
    "jit_param_cast": {"param_cast"},
}


def test_every_program_has_its_own_name(programs):
    assert set(programs) == {"jit_" + p for p in telemetry.DEVICE_PROGRAMS}


@pytest.mark.parametrize("program", ["jit_infer_forward",
                                     "jit_train_grad_sliced"])
def test_programs_that_read_the_weights_do_not_cast_them(
        programs, program_inputs, program):
    """No ``param_cast`` op; the weights come in once, in bfloat16, and in
    float32 comes nothing of their shapes but the gradient carry (the
    matrices: the model's vectors share shapes with the batch's grids).
    The copy is written where the weights are: an output of the update."""
    assert "param_cast" not in _scopes_in(programs[program])
    assert any(n.endswith("param_update/convert_element_type")
               for n in programs["jit_train_apply"])
    # the apply is handed the copy it replaces: that is the weights
    weights = collections.Counter({
        (dtype, shape): n
        for call in program_inputs["jit_train_apply"]
        for (dtype, shape), n in call.items()
        if dtype == "bfloat16" and math.prod(shape) > 1000})
    assert weights
    carries = set()
    for given in program_inputs[program]:
        assert all(given[k] == n for k, n in weights.items())
        wide = sum(given[("float32", shape)] for _, shape in weights)
        assert wide in (0, sum(weights.values()))  # nothing, or the carry
        carries.add(bool(wide))
    assert False in carries  # a step's first grad program has no carry
    assert program != "jit_infer_forward" or carries == {False}


@pytest.mark.parametrize("program", sorted(EXPECTED_SCOPES))
def test_program_carries_its_scopes(programs, program):
    assert _scopes_in(programs[program]) >= EXPECTED_SCOPES[program]
    if program.startswith("jit_train_grad"):
        # the backward pass keeps the names: transpose(jvp(mlp))
        assert any("transpose(" in n and "mlp" in n
                   for n in programs[program])


def test_scope_list_covers_what_the_tiny_path_uses(programs):
    used = set().union(*(_scopes_in(v) for v in programs.values()))
    # "moe" needs an MoE block; everything else the dense step touches
    assert used == set(telemetry.DEVICE_SCOPES) - {"moe"}


def test_span_costs_little_with_no_capture_running():
    """A span outside a capture is a flag check: well under 10 us here
    (0.7-0.8 us measured), i.e. ~150 spans a step cost under 0.1 % of a
    2 s step by two orders of magnitude."""
    import time

    assert not telemetry.enabled()
    n = 20000
    t = time.perf_counter()
    for _ in range(n):
        with telemetry.span("train/upload", real_tokens=1, padded_tokens=2,
                            n_mbs=3, grid="8x512"):
            pass
    assert (time.perf_counter() - t) / n < 10e-6
