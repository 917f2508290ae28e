"""The two grad programs of a packed grid — a step's first micro-batch and
the ones that add into the carry — share ONE trace of the model
(backend/jax_train.py, ``_get_micro_grad_fn``), and what they accumulate
is the plain sum of the micro-batches' gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.api.data import MicroBatchSpec
from areal_tpu.parallel import mesh as pmesh

from test_compute_copy import _engine
from test_remat_plan import _sample, _sq_loss, ledger  # noqa: F401 — fixture

# max tokens a micro-batch that pack the sample (6 sequences of 6-13
# tokens) into 1 and 3 micro-batches
SPECS = {1: MicroBatchSpec(max_tokens_per_mb=128),
         3: MicroBatchSpec(max_tokens_per_mb=32)}


def _weight(mb):
    return mb.n_tokens


def _step_carry(eng, ub, scope="global"):
    """The carry a step hands its apply: (loss, stats, grads); the update
    itself is left out, so the weights stay as they are."""
    taken = []
    eng._apply_and_fetch = lambda carry, *a, **kw: taken.append(carry)
    try:
        eng.train_uniform(ub, _sq_loss, _weight, token_normalize_scope=scope)
    finally:
        del eng._apply_and_fetch
    return taken[0]


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def engine(request):
    return _engine(compute=request.param)


def _scaled_sum(terms, scale, fused):
    """``sum_i terms[i] * scale`` in float32, in order. ``fused``: each
    ``t * scale + sum`` rounded once (a compiler may contract the two ops;
    float32 products and sums are exact in float64 short of one rounding),
    else twice, as numpy does."""
    total = terms[0] * scale
    for t in terms[1:]:
        total = (np.float32(np.float64(t) * np.float64(scale)
                            + np.float64(total))
                 if fused else t * scale + total)
    return total


@pytest.mark.parametrize("n_mbs", sorted(SPECS))
@pytest.mark.parametrize("scope", ["global", "mb"])
def test_a_steps_carry_is_the_plain_sum_bit_for_bit(engine, scope, n_mbs):
    """loss, stats and gradient of a step equal ``sum_i g_i * scale_i`` in
    micro-batch order, done leaf by leaf in numpy on what the shared
    function returns for each micro-batch (the gradient widened to the
    masters' dtype) — bit for bit, a leaf's multiply-adds rounded twice
    or (where the scale is no power of two it can show) once."""
    eng = engine
    ub = eng.upload_uniform(_sample(np.random.RandomState(5)), SPECS[n_mbs])
    assert ub.n_mbs == n_mbs
    loss, stats, grads = jax.device_get(_step_carry(eng, ub, scope))
    glob = scope == "global"
    weights = [float(_weight(mb)) for mb in ub.mbs]
    scale = np.float32(1.0 if glob else 1.0 / n_mbs)
    micro = eng._get_micro_grad_fn(_sq_loss, ub.R, eng._remat_for(ub.R, ub.L))
    mbs = [jax.device_get(micro(
        eng.compute_params(), ub.grids, ub.seq, jnp.asarray(i, jnp.int32),
        jnp.asarray(sum(weights) if glob else w, jnp.float32),
        jnp.asarray((1.0 / n_mbs) if glob else 1.0, jnp.float32),
    )) for i, w in enumerate(weights)]

    def same(got, terms):
        terms = [np.asarray(t) for t in terms]
        return got.dtype == np.float32 and any(
            np.array_equal(got, _scaled_sum(terms, scale, fused))
            for fused in (False, True))

    assert same(loss, [l for (l, _), _ in mbs])
    assert stats == {k: sum(s[k] for (_, s), _ in mbs)
                     for k in mbs[0][0][1]}
    for (path, g), *terms in zip(
            jax.tree_util.tree_leaves_with_path(grads),
            *(jax.tree.leaves(g) for _, g in mbs)):
        assert all(t.dtype == np.float32 for t in terms)  # the masters'
        assert same(g, terms), jax.tree_util.keystr(path)


def _count_model_traces(eng):
    """Calls of the function that holds the model's forward and backward:
    its Python body runs only while jax traces it."""
    calls, real = [], eng._loss_and_grads

    def counted(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    eng._loss_and_grads = counted
    return calls


def test_a_grid_traces_the_model_once_for_its_two_programs(ledger):
    """Steps of three micro-batches or more over two packed grids: the
    compile ledger holds two ``train_grad_sliced`` executables a grid (a
    step's first micro-batch, and the ones that add into the carry), and
    ONE trace a grid of the function they share."""
    eng = _engine()
    traces = _count_model_traces(eng)
    grids = []
    for n, tokens in ((6, 32), (12, 48)):
        ub = eng.upload_uniform(_sample(np.random.RandomState(5), n=n),
                                MicroBatchSpec(max_tokens_per_mb=tokens))
        assert ub.n_mbs >= 3
        grids.append(f"{ub.R}x{ub.L}")
        eng.train_uniform(ub, _sq_loss, _weight)
    assert len(set(grids)) == 2
    programs = ledger.as_dict()["programs"]["train_grad_sliced"]
    assert [(r["label"]["grid"], r["label"]["carry"])
            for r in programs["executables"]] == [
        (g, carry) for g in grids for carry in (False, True)]
    assert programs["n_trace"] == programs["n_compile"] == 4
    assert len(traces) == 2
    # the program traced second spends its trace on its tail alone (a
    # fifth of the first's alone; beside five busy workers these 50-100 ms
    # spans have read 0.39 of it, so the bound is "less", not a ratio)
    spans = [s for s in ledger.as_dict()["spans"]
             if s["fn"] == "train_grad_sliced" and s["stage"] == "trace"]
    assert len(spans) == 4
    for first, second in (spans[:2], spans[2:]):
        assert second["secs"] < first["secs"]


def test_on_a_mesh_the_two_programs_share_the_trace():
    """On a virtual four-device mesh: one trace of the shared function for
    the two programs, and the step agrees with the same engine on one
    device. (Where the carry lives is the partitioner's choice, as before:
    it keeps leaves replicated that the masters shard.)"""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    sample = _sample(np.random.RandomState(5))
    one = _engine(compute="float32")
    want = _step_carry(one, one.upload_uniform(sample, SPECS[3]))
    eng = _engine(compute="float32",
                  mesh=pmesh.make_mesh(pmesh.ParallelSpec.parse("f2t2")))
    masters = jax.tree.leaves(eng.params)
    assert any(not p.sharding.is_fully_replicated for p in masters)
    traces = _count_model_traces(eng)
    got = _step_carry(eng, eng.upload_uniform(sample, SPECS[3]))
    assert len(traces) == 1
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    for g, w in zip(jax.tree.leaves(got[2]), jax.tree.leaves(want[2])):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=1e-5)
