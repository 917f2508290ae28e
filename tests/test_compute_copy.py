"""The compute-dtype copy of the weights is engine state with the lifetime
of a weights version: ``train_apply`` writes it beside the new masters,
every program reads it, any other write of the weights drops it, and the
numbers are those of an engine that casts inside every program."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.api.data import MicroBatchSpec
from areal_tpu.api.model import FinetuneSpec
from areal_tpu.api.train_config import TelemetryConfig
from areal_tpu.backend.jax_train import JaxTrainEngine, OptimizerConfig
from areal_tpu.base import telemetry
from areal_tpu.models import transformer
from areal_tpu.models.config import MoEConfig, tiny_config
from areal_tpu.parallel import mesh as pmesh

from test_remat_plan import _sample as remat_sample
from test_remat_plan import _sq_loss

SPEC = MicroBatchSpec(max_tokens_per_mb=64)  # several micro-batches a step
BF16 = jnp.dtype("bfloat16")


class CastInEveryProgram(JaxTrainEngine):
    """The oracle — the engine before the copy was state: every program
    takes the float32 masters and casts them inside."""

    @property
    def params(self):
        return self._params

    @params.setter
    def params(self, tree):
        self._params, self._compute = tree, None
        self._copy_is_params = True  # the programs are handed the masters

    def _value_and_grad(self, lf, params):
        return super()._value_and_grad(lf, self._cast(params))

    def _model_forward(self, params, batch, **kw):
        return super()._model_forward(self._cast(params), batch, **kw)

    def _forward_token_logprobs(self, params, batch, remat=False):
        return super()._forward_token_logprobs(
            self._cast(params), batch, remat)


def _sample(seed):
    return remat_sample(np.random.RandomState(seed), n=8)


def _lp_loss(logprobs, batch):
    w = (batch["segment_ids"] > 0).astype(jnp.float32)
    return -jnp.sum(logprobs * w), {"n": jnp.sum(w), "big": 2.0 * jnp.sum(w)}


_lp_loss.wants_token_logprobs = True  # the chunked head's program


def _engine(cls=JaxTrainEngine, compute="bfloat16", opt=True, mesh=None,
            dtype=None, **cfg_kw):
    cfg = tiny_config(vocab_size=64, n_layers=2, **cfg_kw)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    if dtype is not None:
        params = jax.tree.map(lambda x: x.astype(dtype), params)
    return cls(
        cfg, params, OptimizerConfig(lr=1e-2) if opt else None,
        FinetuneSpec(1, 8, 4), mesh=mesh, compute_dtype=compute,
        length_bucket=16, rows_bucket=2, seqs_bucket=4, logprob_chunk=8)


def _step(eng, sample, loss=_sq_loss, **kw):
    return eng.train_batch(sample, SPEC, loss, lambda mb: mb.n_tokens, **kw)


def _logprobs(eng, sample):
    return np.concatenate(eng.forward(sample, SPEC, post_hook=_lp_hook))


def _lp_hook(logprobs, batch):
    return logprobs


_lp_hook.wants_token_logprobs = True


def _bits(tree):
    return [np.atleast_1d(np.asarray(x)).view(np.uint8)
            for x in jax.tree.leaves(tree)]


def _assert_same_bits(got, want):
    for g, w in zip(_bits(got), _bits(want), strict=True):
        np.testing.assert_array_equal(g, w)


def _assert_copy_of(eng, masters):
    """The engine's copy is ``astype(compute_dtype)`` of ``masters``."""
    copy = eng.compute_params()
    assert {x.dtype for x in jax.tree.leaves(copy)} == {eng.compute_dtype}
    _assert_same_bits(
        copy, jax.tree.map(lambda x: x.astype(eng.compute_dtype), masters))


def _rebuilds(fn):
    """How far ``fn()`` moved the counter ``train/param_cast_rebuilds``."""
    tel = telemetry.configure("copy", "t", "trainer",
                              cfg=TelemetryConfig(enabled=True), push=False)
    try:
        fn()
        return tel.snapshot(reset=False)["counters"].get(
            "train/param_cast_rebuilds", 0.0)
    finally:
        telemetry.shutdown()


def _steps_match_a_cast_in_every_program(loss):
    """Three optimizer steps of several micro-batches: loss, gradient norm,
    masters and Adam moments to the bit, and one cast in all."""
    eng, ref = _engine(), _engine(CastInEveryProgram)
    for seed in (3, 4, 5):
        got, want = _step(eng, _sample(seed), loss), _step(
            ref, _sample(seed), loss)
        assert got["loss"] == want["loss"]
        assert got["grad_norm"] == want["grad_norm"]
        _assert_same_bits(eng.params, ref.params)
        _assert_same_bits(eng.opt_state, ref.opt_state)
        _assert_copy_of(eng, eng.params)
    assert {x.dtype for x in jax.tree.leaves(eng.params)} == {
        jnp.dtype("float32")}
    assert eng.param_cast_rebuilds == 1  # before the first step; then none
    assert ref.compute_params() is ref.params
    np.testing.assert_array_equal(_logprobs(eng, _sample(6)),
                                  _logprobs(ref, _sample(6)))


def case_a_skipped_update_returns_the_old_copy(tmp_path):
    eng = _engine()
    _step(eng, _sample(3), _lp_loss)
    old = jax.tree.map(np.asarray, eng.params)
    stats = _step(eng, _sample(4), _lp_loss,
                  skip_update_rule=("big", "n", 1.5))  # 2.0 > 1.5: skip
    assert stats["update_applied"] == 0.0
    _assert_same_bits(eng.params, old)
    _assert_copy_of(eng, old)
    stats = _step(eng, _sample(4), _lp_loss,
                  skip_update_rule=("big", "n", 2.5))
    assert stats["update_applied"] == 1.0
    _assert_copy_of(eng, eng.params)
    assert eng.param_cast_rebuilds == 1


def _moved(eng, write):
    """``write(eng)`` replaces the weights from outside train_apply: the
    next forward computes with them, after exactly one rebuild."""
    sample = _sample(6)
    before = _logprobs(eng, sample)
    n = eng.param_cast_rebuilds
    count = _rebuilds(lambda: (write(eng), _logprobs(eng, sample),
                               _logprobs(eng, sample)))
    assert count == 1.0 and eng.param_cast_rebuilds == n + 1
    _assert_copy_of(eng, eng.params)
    after = _logprobs(eng, sample)
    assert np.abs(after - before).max() > 1e-3
    fresh = _engine()
    fresh.params = eng.params
    np.testing.assert_array_equal(_logprobs(fresh, sample), after)


def case_load_train_state_drops_the_copy(tmp_path):
    src = _engine()
    _step(src, _sample(3))
    src.save_train_state(str(tmp_path))
    eng = _engine()
    _moved(eng, lambda e: e.load_train_state(str(tmp_path)))
    _assert_same_bits(eng.params, src.params)
    # and training goes on from the restored copy, as the source does
    assert _step(eng, _sample(4))["loss"] == _step(src, _sample(4))["loss"]


def case_an_external_write_drops_the_copy(tmp_path):
    def ema(eng):  # what the trainer's param_realloc hook does
        eng.params = jax.tree.map(lambda x: x * 1.25, eng.params)

    eng = _engine()
    _step(eng, _sample(3))
    _moved(eng, ema)


def case_nothing_to_cast_means_no_second_tree(tmp_path):
    f32 = _engine(compute="float32")
    assert f32.compute_params() is f32.params
    _step(f32, _sample(3))
    assert f32.compute_params() is f32.params
    frozen = _engine(opt=False, dtype=BF16)  # a reference model in bf16
    assert frozen.compute_params() is frozen.params
    _logprobs(frozen, _sample(6))
    assert f32.param_cast_rebuilds == frozen.param_cast_rebuilds == 0
    # float32 weights without an optimizer: one cast at first use, then none
    ref = _engine(opt=False)
    _logprobs(ref, _sample(6)), _logprobs(ref, _sample(7))
    assert ref.param_cast_rebuilds == 1
    _assert_copy_of(ref, ref.params)


def case_a_shared_copy_outlives_the_next_step(tmp_path):
    """What a weight publish holds is not donated to the next apply."""
    eng = _engine()
    _step(eng, _sample(3))
    held = eng.compute_params(share=True)
    want = jax.tree.map(np.asarray, held)
    _step(eng, _sample(4))
    _assert_same_bits(held, want)  # still alive, still version 1
    assert eng.compute_params() is not held
    _assert_copy_of(eng, eng.params)
    mine = eng.compute_params()
    _step(eng, _sample(5))  # not shared: its buffers go to the new copy
    assert all(x.is_deleted() for x in jax.tree.leaves(mine))
    assert eng.param_cast_rebuilds == 1


def case_the_copy_is_sharded_like_the_masters(tmp_path):
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    mesh = pmesh.make_mesh(pmesh.ParallelSpec.parse("e4"))
    eng = _engine(mesh=mesh, moe=MoEConfig(
        num_experts=4, top_k=2, capacity_factor=None))
    sample = dataclasses.replace(SPEC, max_tokens_per_mb=128)

    def check():
        shardings = [(x.sharding, y.sharding) for x, y in zip(
            jax.tree.leaves(eng.compute_params()),
            jax.tree.leaves(eng.params), strict=True)]
        assert all(c.is_equivalent_to(m, x.ndim) for (c, m), x in zip(
            shardings, jax.tree.leaves(eng.params)))
        assert any(not m.is_fully_replicated for _, m in shardings)

    check()  # rebuilt by ``param_cast``
    eng.train_batch(_sample(3), sample, _sq_loss, lambda mb: mb.n_tokens)
    check()  # written by ``train_apply``
    _assert_copy_of(eng, eng.params)


def case_steps_match_with_a_logits_loss(tmp_path):
    _steps_match_a_cast_in_every_program(_sq_loss)


def case_steps_match_with_the_chunked_head(tmp_path):
    _steps_match_a_cast_in_every_program(_lp_loss)


CASES = [fn for name, fn in sorted(globals().items())
         if name.startswith("case_")]


@pytest.mark.parametrize("case", CASES, ids=lambda fn: fn.__name__[5:])
def test_compute_copy(case, tmp_path):
    case(tmp_path)
