"""What the backward pass re-runs: the entries of
``transformer.REMAT_ENTRIES`` compute the same loss and gradients, the
engine's chooser picks by arithmetic, its estimate matches what jax keeps,
and a program that does not fit falls back one entry."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.api.data import MicroBatchSpec, SequenceSample
from areal_tpu.api.model import FinetuneSpec
from areal_tpu.backend import jax_train
from areal_tpu.backend.jax_train import (
    JaxTrainEngine,
    OptimizerConfig,
    choose_remat,
)
from areal_tpu.models import transformer
from areal_tpu.models.config import MoEConfig, SSMConfig, tiny_config
from areal_tpu.parallel import mesh as pmesh
from areal_tpu.parallel import sharding as psh

ENTRIES = transformer.REMAT_ENTRIES
DENSE = dict(vocab_size=64, n_layers=3)
MOE = dict(vocab_size=64, n_layers=2, moe=MoEConfig(
    num_experts=4, top_k=2, capacity_factor=None))
GB = 1 << 30


def _grid(rng, rows, length, vocab=64):
    tokens = rng.randint(2, vocab, (rows, length)).astype(np.int32)
    seg = np.ones((rows, length), np.int32)
    seg[:, length // 2:] = 2  # two documents a row
    pos = np.concatenate([np.arange(length // 2),
                          np.arange(length - length // 2)])
    return (jnp.asarray(tokens), jnp.asarray(np.tile(pos, (rows, 1))),
            jnp.asarray(seg))


def _loss_and_grads(cfg, params, grid, remat, mesh=None):
    tokens, pos, seg = grid

    def loss(p):
        y, _, aux = transformer.forward(
            p, cfg, tokens, pos, segment_ids=seg, remat=remat,
            return_kv=False, return_aux=True)
        out = jnp.sum(jax.nn.log_softmax(y.astype(jnp.float32)) ** 2)
        return out + (aux["aux_total"] if aux else 0.0)

    fn = jax.jit(jax.value_and_grad(loss))
    if mesh is None:
        return fn(params)
    with psh.activation_sharding(mesh):
        return fn(psh.shard_params(params, mesh, cfg))


# ---- (a) every entry computes what "full" computes ----

@pytest.mark.parametrize("entry", [False, True, *ENTRIES[1:]])
@pytest.mark.parametrize("kind", ["dense", "moe", "moe_ep"])
def test_entries_agree_with_full(kind, entry):
    cfg = tiny_config(**(DENSE if kind == "dense" else MOE))
    mesh = None
    if kind == "moe_ep":
        if len(jax.devices()) < 4:
            pytest.skip("needs 4 devices")
        mesh = pmesh.make_mesh(pmesh.ParallelSpec.parse("d2e2"))
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    grid = _grid(np.random.RandomState(1), 4, 16)
    want_loss, want = _loss_and_grads(cfg, params, grid, "full", mesh)
    got_loss, got = _loss_and_grads(cfg, params, grid, entry, mesh)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-6)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=5e-4, atol=2e-5,
            err_msg=jax.tree_util.keystr(path))


def test_unknown_entry_is_refused():
    cfg = tiny_config(**DENSE)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="dots"):
        _loss_and_grads(cfg, params,
                        _grid(np.random.RandomState(0), 2, 16), "dots")


@pytest.mark.parametrize("entry,calls", [("full", 3), ("attention", 2),
                                         ("matmuls", 2), (False, 2)])
@pytest.mark.parametrize("on_mesh", [False, True])
def test_the_forward_kernel_is_kept_not_rerun(entry, calls, on_mesh):
    """The gradient's jaxpr holds the forward kernel twice under "full"
    (forward, recomputation) beside the fused backward kernel, and once
    where its residuals are kept — also through the kernel's shard_map on
    a mesh. Traced only: the kernel does not run on the CPU."""
    cfg = tiny_config(vocab_size=64, n_layers=2, hidden_dim=128)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    tokens, pos, seg = _grid(np.random.RandomState(0), 4, 256)

    def loss(p):
        y, _ = transformer.forward(p, cfg, tokens, pos, segment_ids=seg,
                                   attn_impl="pallas", remat=entry,
                                   return_kv=False)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    if on_mesh:
        if len(jax.devices()) < 4:
            pytest.skip("needs 4 devices")
        mesh = pmesh.make_mesh(pmesh.ParallelSpec.parse("d2f2"))
        with psh.activation_sharding(mesh):
            jaxpr = jax.make_jaxpr(jax.grad(loss))(params)
    else:
        jaxpr = jax.make_jaxpr(jax.grad(loss))(params)
    text = str(jaxpr)
    assert text.count("pallas_call[") == calls
    assert text.count("name=splash_mqa_fwd") == calls - 1
    assert "flash" not in text


def test_expert_layer_is_never_kept():
    """``ragged_dot`` is not a ``dot_general``: under every entry the
    gradient holds the same grouped GEMMs (the recomputed forward's
    among them), so the benchmark's count of expert passes stays true."""
    cfg = tiny_config(**MOE)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    tokens, pos, seg = _grid(np.random.RandomState(0), 2, 16)
    counts = {}
    for entry in ENTRIES:
        def loss(p):
            y, _ = transformer.forward(p, cfg, tokens, pos, segment_ids=seg,
                                       remat=entry, return_kv=False)
            return jnp.sum(y.astype(jnp.float32) ** 2)

        counts[entry] = str(jax.make_jaxpr(jax.grad(loss))(params)).count(
            "ragged_dot")
    assert counts["full"] > 0
    assert counts["attention"] == counts["matmuls"] == counts["full"]


# ---- (c) the estimate against what jax really keeps ----

def _saved_bytes(cfg, rows, length, entry, attn_impl, cfg_run=None):
    """Bytes of the residuals the layer scan stacks (leading dim
    ``cfg.n_layers``: its steps), as
    ``jax.ad_checkpoint.print_saved_residuals`` lists them, for the model
    ``cfg_run`` (default ``cfg``)."""
    n_steps, cfg = cfg.n_layers, cfg_run or cfg
    from jax._src.ad_checkpoint import saved_residuals

    shapes = jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, jnp.bfloat16), shapes)
    tok = jax.ShapeDtypeStruct((rows, length), jnp.int32)

    def loss(p, tokens, pos, seg):
        y, _ = transformer.forward(p, cfg, tokens, pos, segment_ids=seg,
                                   attn_impl=attn_impl, remat=entry,
                                   return_kv=False, return_hidden=True)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    return sum(
        int(np.prod(aval.shape)) * aval.dtype.itemsize
        for aval, src in saved_residuals(loss, params, tok, tok, tok)
        if "output of scan" in src and aval.shape[0] == n_steps
        and aval.ndim > 2)


QWEN_WIDTHS = dict(vocab_size=512, n_layers=3, hidden_dim=896, n_q_heads=14,
                   n_kv_heads=2, head_dim=64, intermediate_dim=4864)
MOE_WIDTHS = dict(
    vocab_size=512, n_layers=3, hidden_dim=256, n_q_heads=2, n_kv_heads=2,
    head_dim=128, intermediate_dim=128, moe=MoEConfig(
        num_experts=8, top_k=2, capacity_factor=None,
        routed_intermediate_dim=128))


@pytest.mark.parametrize("entry", ENTRIES)
@pytest.mark.parametrize("widths,attn_impl", [
    (QWEN_WIDTHS, "pallas"), (QWEN_WIDTHS, "reference"),
    (MOE_WIDTHS, "pallas")], ids=["qwen-kernel", "qwen-xla", "moe-kernel"])
def test_estimate_matches_what_jax_keeps(widths, attn_impl, entry):
    """Within 5 %: the arithmetic names every kept array (per token and
    layer in the compute dtype; the kernel's output at the PADDED length,
    heads of 64 in 128 lanes, one float32 statistic a head)."""
    cfg = dataclasses.replace(tiny_config(), **widths)
    rows, length = 2, 640  # the kernel pads 640 to 768 (its tile)
    from areal_tpu.ops.attention import kernel_padded_len

    padded = kernel_padded_len(attn_impl, length) or 0
    assert padded == (768 if attn_impl == "pallas" else 0)
    est = transformer.remat_kept_bytes(cfg, rows * length, 2,
                                       full_tokens=rows * padded)
    got = _saved_bytes(cfg, rows, length, entry, attn_impl)
    assert got == pytest.approx(est[entry], rel=0.05)
    assert est["full"] <= est["attention"] <= est["matmuls"]
    if attn_impl == "reference":  # the XLA attention keeps nothing
        assert est["attention"] == est["full"]


def test_estimate_at_the_benchmarks_widths():
    """The issue's arithmetic: per token and layer ~3.7 KB under
    "attention" and ~27 KB of matmul outputs at Qwen2.5-0.5B's widths."""
    cfg = dataclasses.replace(tiny_config(), **{**QWEN_WIDTHS, "n_layers": 1})
    est = transformer.remat_kept_bytes(cfg, 1, 2, full_tokens=1)
    assert est["full"] == 896 * 2
    assert est["attention"] - est["full"] == 14 * (128 * 2 + 4)
    assert est["matmuls"] - est["attention"] == 2 * (
        896 + 128 + 128 + 896 + 4864 + 4864)


# ---- (b) the chooser ----

def test_choose_remat_takes_the_lightest_that_fits():
    kept = {"full": 10, "attention": 20, "matmuls": 100}
    assert choose_remat(kept, 100) == "matmuls"
    assert choose_remat(kept, 99) == "attention"
    assert choose_remat(kept, 20) == "attention"
    assert choose_remat(kept, 19) == "full"  # a byte short
    assert choose_remat(kept, -5) == "full"  # nothing fits: the least


def _engine(cfg=None, remat=True, mesh=None, limit=16 * GB, **kw):
    cfg = cfg or dataclasses.replace(tiny_config(), **QWEN_WIDTHS)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    eng = JaxTrainEngine(
        cfg, params, OptimizerConfig(lr=1e-3), FinetuneSpec(1, 8, 4),
        mesh=mesh, remat=remat, attn_impl="pallas", **kw)
    eng._device_bytes_limit = lambda: limit
    return eng


def test_plan_is_monotone_in_tokens_and_budget():
    eng = _engine()
    rank = {e: i for i, e in enumerate(ENTRIES)}
    by_tokens = [rank[eng._remat_for(1, n)]
                 for n in (1024, 8192, 65536, 262144, 1 << 21)]
    assert by_tokens == sorted(by_tokens, reverse=True)
    assert by_tokens[0] == rank["matmuls"] and by_tokens[-1] == rank["full"]
    by_limit = []
    for limit in (1 * GB, 2 * GB, 4 * GB, 8 * GB, 64 * GB):
        e = _engine(limit=limit)
        by_limit.append(rank[e._remat_for(1, 65536)])
    assert by_limit == sorted(by_limit)
    assert by_limit[0] == rank["full"] and by_limit[-1] == rank["matmuls"]


def test_plan_records_entry_bytes_and_budget():
    eng = _engine()
    entry = eng._remat_for(1, 4096)
    plan = eng.remat_plan()
    assert set(plan) == {"1x4096"}
    rec = plan["1x4096"]
    assert rec["entry"] == entry and rec["fell_back"] is False
    assert rec["kept_bytes_estimate"] == eng._remat_kept_bytes(1, 4096)[entry]
    assert rec["kept_bytes_estimate"] <= rec["budget_bytes"]
    # the budget counts what the engine holds: a fuller chip keeps less
    assert (_engine(limit=64 * GB)._remat_budget_bytes(1, 4096)
            > rec["budget_bytes"])


# What the chip recorded for the benchmark's seven grids, parent and change
# alike (PERF.md §6, PR 28 and 29; `bytes_limit` of `memory_stats()` there):
# (configuration, mesh, bytes_limit, R, L) -> (entry, kept estimate, budget).
# The estimates are PR 45's: a full layer keeps ONE float32 statistic a head
# at the grouped kernel's padded length (7296 runs at 8192, 3712 at 3840);
# entries and budgets are as recorded.
BENCHMARK_GRIDS = {
    ("qwen2.5-0.5b", None, 16909336064, 8, 512):
        ("attention", 533987328, 2247630524),
    ("qwen2.5-0.5b", None, 16909336064, 1, 2688):
        ("matmuls", 1903362048, 2707571183),
    ("qwen2.5-0.5b", None, 16909336064, 1, 3072):
        ("matmuls", 2136932352, 2957127074),
    ("qwen2.5-0.5b", None, 16909336064, 1, 6016):
        ("attention", 795475968, 1620438716),
    ("qwen2.5-0.5b", None, 16909336064, 1, 7296):
        ("attention", 1029439488, 1202310844),
    ("olmoe-1b-7b", "e4", 16909334528, 4, 3712):
        ("matmuls", 369885184, 1857308104),
    ("olmoe-1b-7b", "e4", 16909334528, 4, 3968):
        ("matmuls", 395247616, 1756644808),
}


@pytest.mark.parametrize(
    "grid", sorted(BENCHMARK_GRIDS, key=str),
    ids=lambda g: f"{g[0]}-{g[3]}x{g[4]}")
def test_benchmark_grids_keep_their_entries(grid, monkeypatch):
    """The compute copy moved from a grad program's heap to the engine's
    trees: the budget's sum is the same, so every grid of the benchmark
    keeps the entry — and to a byte the budget — the chip recorded. The
    engine's arithmetic at the real widths, on the bytes of the real
    trees as their shardings cut them (nothing that size is allocated)."""
    import json
    import os

    from benchmark import harness, weights

    name, spec, limit, R, L = grid
    with open(os.path.join(harness.BENCH_DIR, "configs", name + ".json")) as f:
        cfg = weights.model_config(json.load(f))
    mesh = None
    if spec is not None:
        if len(jax.devices()) < 4:
            pytest.skip("needs 4 devices")
        mesh = pmesh.make_mesh(pmesh.ParallelSpec.parse(spec))
    small = dataclasses.replace(
        tiny_config(), **(MOE_WIDTHS if cfg.moe is not None else QWEN_WIDTHS))
    eng = _engine(small, mesh=mesh, limit=limit)  # as the trainer: bf16
    shapes = jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
    if mesh is None:
        masters = sum(x.size for x in jax.tree.leaves(shapes)) * 4
    else:
        masters = sum(
            int(np.prod(sh.shard_shape(x.shape))) * 4
            for x, sh in zip(jax.tree.leaves(shapes), jax.tree.leaves(
                psh.named_shardings(mesh, psh.param_partition_specs(cfg)))))
    counts = 8  # Adam's and the schedule's step counters, int32 each
    trees = {id(eng.params): masters, id(eng.opt_state): 2 * masters + counts}
    monkeypatch.setattr(jax_train, "_bytes_on_chip",
                        lambda tree: trees[id(tree)])
    eng.cfg = cfg
    assert not eng._copy_is_params
    entry, kept, budget = BENCHMARK_GRIDS[grid]
    assert eng._remat_for(R, L) == entry
    rec = eng.remat_plan()[f"{R}x{L}"]
    assert rec["kept_bytes_estimate"] == kept
    assert abs(rec["budget_bytes"] - budget) <= 1  # float order of the sum


def test_full_when_the_device_names_no_limit():
    eng = _engine(limit=None)  # the CPU
    assert eng._remat_for(1, 1024) == "full"
    assert eng.remat_plan()["1x1024"]["budget_bytes"] == 0


def test_tokens_are_counted_per_chip_under_a_mesh():
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    cfg = dataclasses.replace(tiny_config(), **QWEN_WIDTHS)
    one = _engine(cfg)
    mesh = pmesh.make_mesh(pmesh.ParallelSpec.parse("d4"))
    four = _engine(cfg, mesh=mesh)
    assert four.rows_multiple == 4
    assert (four._remat_kept_bytes(4, 2048)
            == one._remat_kept_bytes(1, 2048))
    assert (four._remat_kept_bytes(8, 2048)
            == one._remat_kept_bytes(2, 2048))


def test_gradient_checkpointing_false_bypasses_the_chooser():
    eng = _engine(remat=False)
    assert eng._remat_for(1, 1 << 21) is False
    assert eng.remat_plan() == {}
    assert eng._remat_fall_back(1, 1 << 21) is False


def test_pipeline_stages_keep_full():
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    cfg = tiny_config(vocab_size=64, n_layers=4)
    mesh = pmesh.make_mesh(pmesh.ParallelSpec.parse("p2"))
    eng = _engine(cfg, mesh=mesh, limit=64 * GB)
    from areal_tpu.parallel import pipeline as ppl

    assert ppl.pp_engagement(mesh, cfg, 4, 128)[0] == 1.0
    assert eng._remat_for(4, 128) == "full"
    # the same grid with no pipeline axis keeps all it can
    assert _engine(cfg, limit=64 * GB)._remat_for(4, 128) == "matmuls"


def test_backend_args_pass_gradient_checkpointing_through():
    from areal_tpu.api.cli_args import ModelTrainEvalConfig
    from areal_tpu.experiments.common import backend_args_for

    on = backend_args_for(ModelTrainEvalConfig(path="x"), None, 10)
    off = backend_args_for(
        ModelTrainEvalConfig(path="x", gradient_checkpointing=False),
        None, 10)
    assert on["remat"] is True and off["remat"] is False


# ---- (d) the guard, and the engine's own grad call ----

def _sample(rng, n=6, vocab=64):
    lens = rng.randint(6, 14, n)
    total = int(lens.sum())
    return SequenceSample.from_default(
        ids=[f"s{i}" for i in range(n)],
        data={"packed_input_ids": rng.randint(2, vocab, total).astype(
                  np.int32),
              "loss_mask": np.ones(total, np.float32)},
        seqlens=lens.tolist())


def _sq_loss(logits, batch):
    w = (batch["segment_ids"] > 0).astype(jnp.float32)
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.sum(jnp.sum(lp * lp, axis=-1) * w), {"n": jnp.sum(w)}


def _train_engine(remat, moe=False):
    cfg = tiny_config(**(MOE if moe else DENSE))
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    return JaxTrainEngine(
        cfg, params, OptimizerConfig(type="sgd", lr=1e-2),
        FinetuneSpec(1, 8, 4), compute_dtype="float32", length_bucket=16, rows_bucket=2,
        seqs_bucket=4, remat=remat)


@pytest.mark.parametrize("moe", [False, True], ids=["dense", "moe"])
@pytest.mark.parametrize("entry", ENTRIES)
def test_train_batch_under_every_entry_matches_no_remat(entry, moe,
                                                        monkeypatch):
    """One optimizer step through the engine's own grad programs: the
    same loss, gradient norm and updated weights whatever is kept."""
    sample = _sample(np.random.RandomState(3))
    spec = MicroBatchSpec(max_tokens_per_mb=64)
    ref = _train_engine(False, moe)
    want = ref.train_batch(sample, spec, _sq_loss, lambda mb: mb.n_tokens)
    monkeypatch.setattr(jax_train, "choose_remat", lambda kept, b: entry)
    eng = _train_engine(True, moe)
    got = eng.train_batch(sample, spec, _sq_loss, lambda mb: mb.n_tokens)
    assert {p["entry"] for p in eng.remat_plan().values()} == {entry}
    assert got["loss"] == pytest.approx(want["loss"], rel=1e-5)
    assert got["grad_norm"] == pytest.approx(want["grad_norm"], rel=1e-4)
    for g, w in zip(jax.tree.leaves(eng.params), jax.tree.leaves(ref.params)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-6)


def test_a_program_that_does_not_fit_falls_back_one_entry(monkeypatch):
    """A compile that raises for memory drops the grid one entry towards
    "full", says so in the plan, and traces again; the step completes."""
    monkeypatch.setattr(jax_train, "choose_remat", lambda kept, b: "matmuls")
    eng = _train_engine(True)
    tried = []
    real = eng._get_sliced_grad_fn

    def get_fn(loss_fn, with_carry, R, remat=False):
        fn = real(loss_fn, with_carry, R, remat)

        def call(*args):
            tried.append(remat)
            if remat == "matmuls":
                raise jax.errors.JaxRuntimeError(
                    "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. "
                    "Ran out of memory in memory space hbm.")
            return fn(*args)

        return call

    monkeypatch.setattr(eng, "_get_sliced_grad_fn", get_fn)
    stats = eng.train_batch(_sample(np.random.RandomState(3)),
                            MicroBatchSpec(max_tokens_per_mb=64), _sq_loss,
                            lambda mb: mb.n_tokens)
    assert np.isfinite(stats["loss"])
    assert tried[:2] == ["matmuls", "attention"]
    assert "matmuls" not in tried[2:]  # the grid stays where it fell
    (rec,) = eng.remat_plan().values()
    assert rec["entry"] == "attention" and rec["fell_back"] is True
    assert rec["kept_bytes_estimate"] <= eng._remat_kept_bytes(
        *map(int, next(iter(eng.remat_plan())).split("x")))["matmuls"]


def test_other_errors_and_the_last_entry_are_not_swallowed(monkeypatch):
    eng = _train_engine(True)  # the CPU names no limit: "full"
    sample = _sample(np.random.RandomState(3))
    spec = MicroBatchSpec(max_tokens_per_mb=64)

    def boom(msg):
        def get_fn(loss_fn, with_carry, R, remat=False):
            def call(*args):
                raise jax.errors.JaxRuntimeError(msg)
            return call
        return get_fn

    monkeypatch.setattr(eng, "_get_sliced_grad_fn", boom("RESOURCE_EXHAUSTED: hbm"))
    with pytest.raises(jax.errors.JaxRuntimeError, match="RESOURCE"):
        eng.train_batch(sample, spec, _sq_loss, lambda mb: mb.n_tokens)
    assert all(not p["fell_back"] for p in eng.remat_plan().values())
    monkeypatch.setattr(eng, "_get_sliced_grad_fn", boom("INTERNAL: other"))
    with pytest.raises(jax.errors.JaxRuntimeError, match="INTERNAL"):
        eng.train_batch(sample, spec, _sq_loss, lambda mb: mb.n_tokens)


def test_fwd_bwd_span_names_the_entry():
    from areal_tpu.api.train_config import TelemetryConfig
    from areal_tpu.base import telemetry

    tel = telemetry.configure("remat", "t", "trainer",
                              cfg=TelemetryConfig(enabled=True), push=False)
    try:
        for remat, want in ((True, "full"), (False, "False")):
            _train_engine(remat).train_batch(
                _sample(np.random.RandomState(3)),
                MicroBatchSpec(max_tokens_per_mb=64), _sq_loss,
                lambda mb: mb.n_tokens)
            spans = [s for s in tel.registry.snapshot(reset=True)["spans"]
                     if s["name"] == "train/fwd_bwd"]
            assert spans and all(s["attrs"]["remat"] == want for s in spans)
    finally:
        telemetry.shutdown()


# ---- (e) a layer pattern: the windowed kernel under "attention" ----

MELLUM_WIDTHS = dict(
    vocab_size=512, n_layers=4, hidden_dim=256, n_q_heads=8, n_kv_heads=2,
    head_dim=128, intermediate_dim=128, sliding_window=256,
    layer_types=("sliding", "sliding", "sliding", "full"),
    moe=MoEConfig(num_experts=4, top_k=2, capacity_factor=None,
                  routed_intermediate_dim=128, router_experts=16,
                  first_expert=4))


def _kernel_calls(jaxpr):
    """The name of every ``pallas_call`` in a jaxpr, through its nested
    jaxprs (a scan's body counts once: one period of the pattern)."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(str(eqn.params["name"]))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    names += _kernel_calls(sub)
    return names


@pytest.mark.parametrize("entry,calls", [("full", 4), ("attention", 3),
                                         ("matmuls", 3), (False, 3)])
def test_window_forward_is_kept_not_rerun(entry, calls):
    """A period of the pattern in the gradient's jaxpr: each of its four
    layers holds its kernel's forward twice under "full" and once where
    the residuals are kept, by their checkpoint name — beside dKV and dQ
    on a windowed layer, and the one fused backward kernel on the full
    one. No flash kernel is in it."""
    cfg = dataclasses.replace(tiny_config(), **MELLUM_WIDTHS)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    tokens, pos, seg = _grid(np.random.RandomState(0), 2, 512, vocab=512)

    def loss(p):
        y, _ = transformer.forward(p, cfg, tokens, pos, segment_ids=seg,
                                   attn_impl="pallas", remat=entry,
                                   return_kv=False)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    names = _kernel_calls(jax.make_jaxpr(jax.grad(loss))(params).jaxpr)
    assert len(names) == 3 * calls + (calls - 1)
    assert sum("splash_mqa_fwd" in n for n in names) == 4 * (calls - 2)
    assert sum("splash_mqa_dkv" in n for n in names) == 4
    assert sum("splash_mqa_dq" in n for n in names) == 3  # the windowed
    assert all("splash" in n for n in names)


@pytest.mark.parametrize("entry", ENTRIES)
def test_estimate_matches_what_jax_keeps_under_a_pattern(entry):
    """Three windowed layers and a full one (output in 128 lanes and ONE
    float32 statistic a head, each at its own tile rule's padded length);
    the router's logits are 16 wide on a share that holds 4 experts."""
    from areal_tpu.ops.attention import kernel_padded_len

    cfg = dataclasses.replace(  # three periods
        tiny_config(), **{**MELLUM_WIDTHS, "n_layers": 12,
                          "layer_types": MELLUM_WIDTHS["layer_types"] * 3})
    rows, length = 2, 640
    full = kernel_padded_len("pallas", length)
    window = kernel_padded_len("pallas", length, cfg.sliding_window)
    assert (full, window) == (768, 768)
    est = transformer.remat_kept_bytes(
        cfg, rows * length, 2, full_tokens=rows * full,
        window_tokens=rows * window)
    assert est["attention"] - est["full"] == 3 * rows * 768 * 8 * (
        4 * (128 * 2 + 4))
    # the scan runs over PERIODS: what it stacks has a leading dim of 3,
    # and each layer of a period keeps its own arrays
    periods = dataclasses.replace(cfg, n_layers=3, layer_types=None)
    got = _saved_bytes(periods, rows, length, entry, "pallas", cfg_run=cfg)
    assert got == pytest.approx(est[entry], rel=0.05)


def test_plan_counts_both_kernels_of_a_pattern():
    eng = _engine(dataclasses.replace(tiny_config(), **MELLUM_WIDTHS))
    kept = eng._remat_kept_bytes(2, 640)
    assert kept == transformer.remat_kept_bytes(
        eng.cfg, 2 * 640, 2, full_tokens=2 * 768, window_tokens=2 * 768)
    assert eng._remat_for(2, 640) in ENTRIES
    assert eng._layer_kinds == "sliding,sliding,sliding,full"


# ---- (f) layers that are one mixer each: a tree per kind ----

HYBRID_WIDTHS = dict(
    vocab_size=512, n_layers=6, hidden_dim=256, n_q_heads=2, n_kv_heads=1,
    head_dim=128, intermediate_dim=128, pos_embedding="none",
    layer_types=("moe_only", "mamba", "attention_only") * 2,
    ssm=SSMConfig(n_heads=4, head_dim=32, n_groups=1, state_dim=32,
                  chunk_size=128),
    moe=MoEConfig(num_experts=2, top_k=4, capacity_factor=None,
                  routed_intermediate_dim=128, router_experts=16,
                  shared_intermediate_dim=384, router_score="sigmoid",
                  routed_scaling_factor=2.5, latent_dim=128,
                  gated_experts=False, expert_act="relu2",
                  aux_loss_coeff=0.0))


@pytest.mark.parametrize("entry", ENTRIES)
def test_estimate_matches_what_jax_keeps_of_mixer_layers(entry):
    """Two periods of (expert layer, Mamba-2, attention): every layer
    keeps its input; the attention layer the kernel's output and
    statistic; under ``matmuls`` the in-projection, q/k/v, and the
    router's logits, the latent down-projection and the shared expert's
    first matmul — the scan's products carry batch dimensions and the last
    matmul of a mixer feeds the residual sum alone."""
    from areal_tpu.ops.attention import kernel_padded_len

    cfg = dataclasses.replace(tiny_config(), **HYBRID_WIDTHS)
    rows, length = 2, 640
    full = kernel_padded_len("pallas", length)
    est = transformer.remat_kept_bytes(cfg, rows * length, 2,
                                       full_tokens=rows * full)
    tok = rows * length
    assert est["full"] == 6 * tok * 256 * 2
    assert est["attention"] - est["full"] == 2 * rows * 768 * 2 * (
        128 * 2 + 4)
    assert est["matmuls"] - est["attention"] == 2 * tok * 2 * (
        (16 + 128 + 384) + cfg.ssm.in_proj_dim + (256 + 2 * 128))
    # the scan runs over the two PERIODS
    periods = dataclasses.replace(cfg, n_layers=2, layer_types=None)
    got = _saved_bytes(periods, rows, length, entry, "pallas", cfg_run=cfg)
    assert got == pytest.approx(est[entry], rel=0.06)


def test_plan_of_mixer_layers_reads_its_costs_off_the_config():
    eng = _engine(dataclasses.replace(tiny_config(), **HYBRID_WIDTHS))
    assert eng._layer_kinds == "moe_only,mamba,attention_only"
    # the costliest mixer of: the expert layer's top_k rows at the latent
    # width, its shared expert, the scan's float32 [heads, chunk] decays
    # (wider than the in-projection here), attention's q
    assert eng._mixer_layer_width() == max(
        4 * 128 * jax_train._LATENT_MOE_LAYER_COPIES,
        384 * jax_train._LAYER_COPIES,
        2 * 4 * 128 * jax_train._LAYER_COPIES,
        256 * jax_train._LAYER_COPIES) == 9216
    wide = dataclasses.replace(eng.cfg, moe=dataclasses.replace(
        eng.cfg.moe, top_k=22, latent_dim=1024))
    assert _engine(wide)._mixer_layer_width() == 22 * 1024 * 11
    assert eng._remat_for(2, 640) in ENTRIES
    assert eng.remat_plan()["2x640"]["kept_bytes_estimate"] == (
        eng._remat_kept_bytes(2, 640)[eng._remat_for(2, 640)])


# ---- (g) whole blocks whose FFN kinds differ: a tree per kind ----

AFMOE_WIDTHS = dict(
    vocab_size=512, n_layers=10, hidden_dim=256, n_q_heads=4, n_kv_heads=2,
    head_dim=128, intermediate_dim=384, sliding_window=256,
    layer_types=("sliding", "sliding", "sliding", "sliding", "full") * 2,
    mlp_layer_types=("dense", "sparse", "sparse", "sparse", "sparse") * 2,
    layer_rope=(("full", None),), use_qk_norm=True, gated_attention=True,
    sandwich_norm=True, scale_embeddings=True,
    moe=MoEConfig(num_experts=2, top_k=4, capacity_factor=None,
                  routed_intermediate_dim=128, router_experts=16,
                  shared_intermediate_dim=128, router_score="sigmoid",
                  routed_scaling_factor=2.826, aux_loss_coeff=0.0))


@pytest.mark.parametrize("entry", ENTRIES)
def test_estimate_matches_what_jax_keeps_of_blocks_that_differ_in_ffn(entry):
    """Two periods of (S·dense, S, S, S, F): every block keeps its input;
    the kernel's output and statistic, sliding and full ones alike; under
    ``matmuls`` q/k/v, the attention gate
    and o_proj of every block, gate and up of the dense FFN, on an expert
    block the router's logits and the shared expert's pair, and the
    FFN's last matmul, which the sandwich norm behind it reads."""
    from areal_tpu.ops.attention import kernel_padded_len

    cfg = dataclasses.replace(tiny_config(), **AFMOE_WIDTHS)
    assert cfg.layer_kinds[:5] == ("sliding_dense", "sliding", "sliding",
                                   "sliding", "full")
    rows, length = 2, 640
    full = kernel_padded_len("pallas", length)
    window = kernel_padded_len("pallas", length, cfg.sliding_window)
    est = transformer.remat_kept_bytes(
        cfg, rows * length, 2, full_tokens=rows * full,
        window_tokens=rows * window)
    tok = rows * length
    assert est["full"] == 10 * tok * 256 * 2
    assert est["attention"] - est["full"] == 2 * rows * 768 * 4 * (
        5 * (128 * 2 + 4))
    # q, k and v, o_proj, the gate, and the FFN's last matmul (dense: down;
    # experts: the shared expert's), which its post-norm reads
    attn = 512 + 2 * 256 + 256 + 512 + 256
    assert est["matmuls"] - est["attention"] == 2 * tok * 2 * (
        5 * attn + 2 * 384 + 4 * (16 + 2 * 128))
    # the scan runs over the two PERIODS
    periods = dataclasses.replace(cfg, n_layers=2, layer_types=None,
                                  mlp_layer_types=None)
    got = _saved_bytes(periods, rows, length, entry, "pallas", cfg_run=cfg)
    assert got == pytest.approx(est[entry], rel=0.06)


def test_plan_of_blocks_that_differ_in_ffn_reads_its_costs_off_the_config():
    eng = _engine(dataclasses.replace(tiny_config(), **AFMOE_WIDTHS))
    assert eng._layer_kinds == "sliding_dense,sliding,sliding,sliding,full"
    # the costliest half of a block: the dense FFN's width, an expert
    # layer's top_k rows at the hidden width, the shared expert, q
    assert eng._mixer_layer_width() == max(
        384 * jax_train._LAYER_COPIES,
        4 * 256 * jax_train._MOE_LOCAL_LAYER_COPIES,
        128 * jax_train._LAYER_COPIES, 512 * jax_train._LAYER_COPIES)
    assert eng._remat_for(2, 640) in ENTRIES


def test_fwd_bwd_span_counts_the_documents_that_begin_inside_a_row():
    from areal_tpu.api.train_config import TelemetryConfig
    from areal_tpu.base import telemetry

    cfg = dataclasses.replace(
        tiny_config(vocab_size=64), pos_embedding="none",
        layer_types=("mamba", "attention_only"),
        ssm=SSMConfig(n_heads=2, head_dim=8, n_groups=1, state_dim=8,
                      chunk_size=8))
    eng = JaxTrainEngine(
        cfg, transformer.init_params(cfg, jax.random.PRNGKey(0)),
        OptimizerConfig(type="sgd", lr=1e-2), FinetuneSpec(1, 8, 4),
        compute_dtype="float32", length_bucket=16, rows_bucket=2,
        seqs_bucket=4, remat=True)
    tel = telemetry.configure("ssm", "t", "trainer",
                              cfg=TelemetryConfig(enabled=True), push=False)
    try:
        eng.train_batch(_sample(np.random.RandomState(3)),
                        MicroBatchSpec(max_tokens_per_mb=64), _sq_loss,
                        lambda mb: mb.n_tokens)
        snap = tel.registry.snapshot(reset=True)
        spans = [s for s in snap["spans"] if s["name"] == "train/fwd_bwd"]
        assert spans and all(
            s["attrs"]["layer_kinds"] == "mamba,attention_only"
            for s in spans)
        starts = sum(s["attrs"]["ssm_segment_starts"] for s in spans)
        assert starts > 0  # 6 sequences in rows of at most 64 tokens
    finally:
        telemetry.shutdown()
    from areal_tpu.models import ssm

    assert any(g[2:] == (8, 2, 1) for g in ssm.geometry_counts())


# ---- (h) the plan shows the engine's reckoning beside the compiler's ----

@pytest.fixture
def ledger(monkeypatch):
    """A compile ledger of the test's own as the process's: it listens to
    jax and asks the backend for its executables' statistics."""
    from areal_tpu.base import compile_watch as cw

    led = cw.CacheStats(cw.jax_live_executables)
    jax.monitoring.register_scalar_listener(led._on_enter)
    jax.monitoring.register_event_time_span_listener(led._on_span)
    monkeypatch.setattr(cw, "_CACHE_STATS", led)
    try:
        yield led
    finally:
        jax.monitoring.unregister_scalar_listener(led._on_enter)
        jax.monitoring.unregister_event_time_span_listener(led._on_span)


def _grad_compiles(led):
    return led.as_dict()["programs"]["train_grad_sliced"]["n_compile"]


def test_plan_holds_reckoned_and_compiled_and_the_label_compiles_nothing(
        ledger, monkeypatch):
    from areal_tpu.base import compile_watch as cw

    sample = _sample(np.random.RandomState(3))
    spec = MicroBatchSpec(max_tokens_per_mb=32)  # two micro-batches
    eng = _train_engine(True)
    eng.train_batch(sample, spec, _sq_loss, lambda mb: mb.n_tokens)
    ((grid, rec),) = eng.remat_plan().items()
    R, L = map(int, grid.split("x"))
    assert rec["entry"] == "full"  # the CPU names no limit
    assert rec["reckoned_heap_bytes"] == eng._reckoned_heap_bytes(
        R, L, rec["kept_bytes_estimate"]) > rec["kept_bytes_estimate"] > 0
    # the compiler's side, of the grid's grad program that needs most
    records = ledger.executables("train_grad_sliced")
    assert [r["label"]["carry"] for r in records] == [False, True]
    for r in records:
        assert r["label"] == {
            "grid": grid, "carry": r["label"]["carry"], "remat": "full",
            **{k: rec[k] for k in jax_train._PLAN_LABEL}}
        assert r["temp_bytes"] > 0 and r["peak_bytes"] > 0
    assert rec["compiled"] == {
        "temp_bytes": max(r["temp_bytes"] for r in records),
        "peak_bytes": max(r["peak_bytes"] for r in records),
        "cache": records[0]["cache"]}
    # every program the step compiled was found; the other labelled ones
    d = ledger.as_dict()
    assert d["executables_unmatched"] == 0
    (apply,) = d["programs"]["train_apply"]["executables"]
    assert apply["label"] == {"skip_rule": False}
    # a second step compiles nothing and leaves the record as it is
    n = _grad_compiles(ledger)
    eng.train_batch(sample, spec, _sq_loss, lambda mb: mb.n_tokens)
    assert _grad_compiles(ledger) == n == 2
    assert eng.remat_plan()[grid] == rec
    # an engine whose label is never stored compiles as many programs
    monkeypatch.setattr(cw, "label", lambda fn, **fields: None)
    other = _train_engine(True)
    other.train_batch(sample, spec, _sq_loss, lambda mb: mb.n_tokens)
    assert _grad_compiles(ledger) == 2 * n
    assert [r["label"] for r in ledger.executables("train_grad_sliced")[n:]
            ] == [{}, {}]


def test_inference_programs_are_labelled_by_call_site_state(ledger):
    eng = _train_engine(False)
    sample = _sample(np.random.RandomState(3))
    spec = MicroBatchSpec(max_tokens_per_mb=64)

    def hook(out, batch):
        return out.sum(-1)

    eng.forward(sample, spec)
    eng.forward(sample, spec, post_hook=hook)
    labels = [r["label"] for r in ledger.executables("infer_forward")]
    grids = {lab["grid"] for lab in labels}
    assert len(grids) == 1 and len(next(iter(grids)).split("x")) == 2
    assert [(lab["use_lp"], lab["hook"]) for lab in labels] == [
        (False, False), (False, True)]
    assert eng.remat_plan() == {}  # remat off: nothing reckoned


def test_fall_back_keeps_what_was_reckoned_for_the_entry_that_failed(
        monkeypatch):
    monkeypatch.setattr(jax_train, "choose_remat", lambda kept, b: "matmuls")
    eng = _train_engine(True)
    eng._remat_for(2, 64)
    first = dict(eng.remat_plan()["2x64"])
    assert eng._remat_fall_back(2, 64) and eng._remat_fall_back(2, 64)
    rec = eng.remat_plan()["2x64"]
    assert rec["entry"] == "full" and rec["fell_back"] is True
    assert [f["entry"] for f in rec["failed"]] == ["matmuls", "attention"]
    assert rec["failed"][0] == {k: first[k] for k in (
        "entry", "kept_bytes_estimate", "budget_bytes",
        "reckoned_heap_bytes")}
    assert (rec["failed"][0]["reckoned_heap_bytes"]
            > rec["failed"][1]["reckoned_heap_bytes"]
            >= rec["reckoned_heap_bytes"] > 0)
