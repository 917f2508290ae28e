"""Train-engine tests (role of the reference's mock_train-backed tests)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.api.data import MicroBatchSpec, SequenceSample
from areal_tpu.api.model import FinetuneSpec, GenerationHyperparameters
from areal_tpu.backend import microbatch as mbu
from areal_tpu.backend.jax_train import (
    JaxTrainEngine,
    OptimizerConfig,
    build_lr_schedule,
)
from areal_tpu.models import transformer
from areal_tpu.models.config import tiny_config
from areal_tpu.parallel import mesh as pmesh


def _sample(rng, n, vocab=64, minlen=4, maxlen=20):
    lens = rng.randint(minlen, maxlen, n)
    toks = rng.randint(2, vocab, int(lens.sum())).astype(np.int32)
    mask = rng.rand(int(lens.sum())) > 0.2
    return SequenceSample.from_default(
        ids=[f"s{i}" for i in range(n)],
        data={
            "packed_input_ids": toks,
            "loss_mask": mask.astype(np.float32),
        },
        seqlens=lens.tolist(),
    )


def _ce_loss(logits, batch):
    """Next-token CE summed over masked positions."""
    tokens = batch["tokens"]
    seg = batch["segment_ids"]
    labels = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    nxt_seg = jnp.concatenate([seg[:, 1:], jnp.zeros_like(seg[:, :1])], axis=1)
    valid = (nxt_seg == seg) & (seg > 0)  # next token exists in same doc
    lm = batch["loss_mask"]
    lmask = jnp.concatenate([lm[:, 1:], jnp.zeros_like(lm[:, :1])], axis=1)
    w = valid * lmask
    lp = jax.nn.log_softmax(logits, axis=-1)
    tok_lp = jnp.take_along_axis(lp, labels[..., None], axis=-1)[..., 0]
    loss = -jnp.sum(tok_lp * w)
    return loss, {"n_valid": jnp.sum(w)}


def _weight(mb):
    return float(mb.grids["loss_mask"].sum())


def test_microbatch_split_and_scatter_roundtrip():
    rng = np.random.RandomState(0)
    s = _sample(rng, 9)
    mbs = mbu.split_into_microbatches(
        s, MicroBatchSpec(max_tokens_per_mb=64), length_bucket=16, rows_bucket=2
    )
    assert len(mbs) >= 2
    # reconstruct tokens via scatter_back on the token grids themselves
    outs = [mb.grids["tokens"] for mb in mbs]
    per_sample = mbu.scatter_back(mbs, outs, s.bs)
    flat = np.concatenate(per_sample)
    np.testing.assert_array_equal(flat, s.data["packed_input_ids"])


def test_lr_schedule_shapes():
    cfg = OptimizerConfig(lr=1e-3, warmup_steps_proportion=0.1,
                          lr_scheduler_type="cosine", min_lr_ratio=0.1)
    sched = build_lr_schedule(cfg, 100)
    assert float(sched(0)) == pytest.approx(0.0)
    assert float(sched(10)) == pytest.approx(1e-3, rel=1e-2)
    assert float(sched(100)) == pytest.approx(1e-4, rel=1e-2)


def test_chunked_logprob_head_parity():
    """The chunked-logprob head (engine._forward_token_logprobs) must match
    the full-logits path exactly — outputs AND gradients — for every chunk
    size, including C == L (checkpoint-only) and C < L (lax.map)."""
    cfg = tiny_config(vocab_size=64)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    R, L = 2, 32
    batch = {
        "tokens": jnp.asarray(rng.randint(0, 64, (R, L)), jnp.int32),
        "positions": jnp.tile(jnp.arange(L, dtype=jnp.int32), (R, 1)),
        "segment_ids": jnp.asarray(
            np.where(np.arange(L) < 28, 1, 0)[None].repeat(R, 0), jnp.int32
        ),
    }
    from areal_tpu.algorithms import ppo_functional as F

    def full_lp(eng, p):
        logits = eng._model_forward(p, batch)
        return F.token_logprobs_from_logits(
            logits, batch["tokens"], batch["segment_ids"]
        )

    ref_eng = JaxTrainEngine(cfg, params, compute_dtype="float32",
                             logprob_chunk=None)
    ref = full_lp(ref_eng, ref_eng.params)
    g_ref = jax.grad(lambda p: jnp.sum(full_lp(ref_eng, p) ** 2))(
        ref_eng.params
    )
    for chunk in (8, 16, 32, 64):
        eng = JaxTrainEngine(cfg, params, compute_dtype="float32",
                             logprob_chunk=chunk)
        lp, aux = eng._forward_token_logprobs(eng.params, batch)
        np.testing.assert_allclose(np.asarray(lp), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)
        g = jax.grad(
            lambda p: jnp.sum(eng._forward_token_logprobs(p, batch)[0] ** 2)
        )(eng.params)
        for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)


def test_scale_by_adam_mixed_matches_optax():
    """The mixed-dtype Adam (backend.scale_by_adam_mixed) with f32 moments
    must match optax.adamw exactly; bf16 moments track within bf16 noise."""
    import optax

    from areal_tpu.backend.jax_train import scale_by_adam_mixed

    rng = np.random.RandomState(0)
    params = {"w": jnp.asarray(rng.randn(8, 4).astype(np.float32)),
              "b": jnp.asarray(rng.randn(4).astype(np.float32))}
    grads_seq = [
        {"w": jnp.asarray(rng.randn(8, 4).astype(np.float32) * 0.1),
         "b": jnp.asarray(rng.randn(4).astype(np.float32) * 0.1)}
        for _ in range(5)
    ]
    ref = optax.chain(
        optax.scale_by_adam(b1=0.9, b2=0.95, eps=1e-5),
        optax.add_decayed_weights(0.05),
        optax.scale_by_learning_rate(1e-3),
    )
    ours = optax.chain(
        scale_by_adam_mixed(0.9, 0.95, 1e-5),
        optax.add_decayed_weights(0.05),
        optax.scale_by_learning_rate(1e-3),
    )
    bf = optax.chain(
        scale_by_adam_mixed(0.9, 0.95, 1e-5, mu_dtype="bfloat16",
                            nu_dtype="bfloat16"),
        optax.add_decayed_weights(0.05),
        optax.scale_by_learning_rate(1e-3),
    )
    p_ref, p_ours, p_bf = params, params, params
    s_ref, s_ours, s_bf = ref.init(params), ours.init(params), bf.init(params)
    for g in grads_seq:
        u, s_ref = ref.update(g, s_ref, p_ref)
        p_ref = optax.apply_updates(p_ref, u)
        u, s_ours = ours.update(g, s_ours, p_ours)
        p_ours = optax.apply_updates(p_ours, u)
        u, s_bf = bf.update(g, s_bf, p_bf)
        p_bf = optax.apply_updates(p_bf, u)
    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_ours)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-5)
    # bf16-moment trajectory stays close (state rounding only)
    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_bf)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=2e-2)
    # storage dtypes honored
    assert str(jax.tree.leaves(s_bf[0].mu)[0].dtype) == "bfloat16"
    assert str(jax.tree.leaves(s_bf[0].nu)[0].dtype) == "bfloat16"
    assert str(jax.tree.leaves(s_ours[0].mu)[0].dtype) == "float32"


def test_weight_decay_passes_over_a_models_buffers():
    """``build_optimizer``'s decay is optax's — the same state tree, the
    same updates — on every leaf but a model's buffers
    (``moe.BUFFER_LEAVES``: the router's choice bias), which a step with
    no gradient leaves bit for bit."""
    import optax

    from areal_tpu.api.train_config import OptimizerConfig
    from areal_tpu.backend import jax_train

    rng = np.random.RandomState(0)
    params = {"layers": {"moe_only": {
        "router": jnp.asarray(rng.randn(2, 8, 4).astype(np.float32)),
        "router_bias": jnp.asarray(rng.randn(2, 4).astype(np.float32))}},
        "final_ln": jnp.ones(8)}
    ours = jax_train.add_decayed_weights(0.05)
    theirs = optax.add_decayed_weights(0.05)
    assert (jax.tree.structure(ours.init(params))
            == jax.tree.structure(theirs.init(params)))
    grads = jax.tree.map(lambda p: 0.1 * jnp.ones_like(p), params)
    got, _ = ours.update(grads, ours.init(params), params)
    want, _ = theirs.update(grads, theirs.init(params), params)
    want["layers"]["moe_only"]["router_bias"] = grads[
        "layers"]["moe_only"]["router_bias"]
    jax.tree.map(np.testing.assert_array_equal, got, want)

    tx, _ = jax_train.build_optimizer(
        OptimizerConfig(lr=1e-2, weight_decay=0.1), total_steps=10)
    zero = jax.tree.map(jnp.zeros_like, params)
    updates, _ = tx.update(zero, tx.init(params), params)
    moe_updates = updates["layers"]["moe_only"]
    assert not np.any(np.asarray(moe_updates["router_bias"]))
    assert np.all(np.asarray(moe_updates["router"]) != 0)


@pytest.mark.parametrize("mesh_spec", [None, "d2f2t2"])
def test_train_batch_reduces_loss(mesh_spec):
    rng = np.random.RandomState(1)
    cfg = tiny_config(vocab_size=64)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    mesh = pmesh.make_mesh(pmesh.ParallelSpec.parse(mesh_spec)) if mesh_spec else None
    eng = JaxTrainEngine(
        cfg, params,
        opt_cfg=OptimizerConfig(lr=1e-2, lr_scheduler_type="constant",
                                warmup_steps_proportion=0.0),
        ft_spec=FinetuneSpec(1, 64, 8),
        mesh=mesh, compute_dtype="float32", length_bucket=16, rows_bucket=2,
    )
    s = _sample(rng, 8)
    spec = MicroBatchSpec(max_tokens_per_mb=64)
    losses = [
        eng.train_batch(s, spec, _ce_loss, _weight)["loss"] for _ in range(8)
    ]
    assert losses[-1] < losses[0] * 0.9, losses
    assert eng.opt_step_count == 8


@pytest.mark.ring
def test_train_batch_ppsp_matches_dense():
    """Step-0 train_batch parity, PP∘SP (p2s2) vs dense.

    Regression pin for the sp-sharded loss miscompile: jax 0.4.x GSPMD
    summed per-shard partials of a next-token-shift concatenate along an
    sp-sharded dim, so on pp×sp meshes the CE mask came back doubled and
    every position invalid (loss -0.0, n_valid 0). The engine now keeps
    the sequence dim unsharded outside manual regions; this test fails
    if that regresses.
    """
    rng = np.random.RandomState(1)
    cfg = tiny_config(vocab_size=64)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    # 8 seqs of exactly 16 tokens -> packer picks [R=8, L=16]: pp=2
    # engages (8 % 2 == 0) and ring engages (16 % 2*sp == 0).
    s = _sample(rng, 8, minlen=16, maxlen=17)
    spec = MicroBatchSpec(max_tokens_per_mb=128)
    stats = {}
    for label, mesh_spec in [("p2s2", "p2s2"), (None, None)]:
        mesh = (pmesh.make_mesh(pmesh.ParallelSpec.parse(mesh_spec))
                if mesh_spec else None)
        eng = JaxTrainEngine(
            cfg, jax.tree.map(jnp.copy, params),  # train_batch donates
            opt_cfg=OptimizerConfig(lr=1e-2, lr_scheduler_type="constant",
                                    warmup_steps_proportion=0.0),
            ft_spec=FinetuneSpec(1, 64, 8),
            mesh=mesh, compute_dtype="float32",
            length_bucket=16, rows_bucket=4,
        )
        stats[label] = eng.train_batch(s, spec, _ce_loss, _weight)
    assert stats["p2s2"]["n_valid"] == stats[None]["n_valid"] > 0
    np.testing.assert_allclose(stats["p2s2"]["loss"], stats[None]["loss"],
                               rtol=1e-4)
    np.testing.assert_allclose(stats["p2s2"]["grad_norm"],
                               stats[None]["grad_norm"], rtol=1e-3)


def test_forward_logprobs_match_direct():
    rng = np.random.RandomState(2)
    cfg = tiny_config(vocab_size=32)
    params = transformer.init_params(cfg, jax.random.PRNGKey(1))
    eng = JaxTrainEngine(cfg, params, compute_dtype="float32",
                         length_bucket=16, rows_bucket=1)
    s = _sample(rng, 5, vocab=32)

    def logprob_hook(logits, batch):
        tokens = batch["tokens"]
        labels = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
        lp = jax.nn.log_softmax(logits, axis=-1)
        return jnp.take_along_axis(lp, labels[..., None], axis=-1)[..., 0]

    per_sample = eng.forward(s, MicroBatchSpec(max_tokens_per_mb=48),
                             post_hook=logprob_hook)
    assert len(per_sample) == 5
    # check one sample against direct single-sequence forward
    i = 3
    toks = s.data["packed_input_ids"][
        s.offsets("packed_input_ids")[i] : s.offsets("packed_input_ids")[i]
        + s.total_lens()[i]
    ]
    T = len(toks)
    logits, _ = transformer.forward(
        jax.tree.map(jnp.asarray, params), cfg,
        jnp.asarray(toks[None]), jnp.arange(T)[None],
        segment_ids=jnp.ones((1, T), jnp.int32),
    )
    lp = jax.nn.log_softmax(logits[0], axis=-1)
    want = np.asarray(
        jnp.take_along_axis(lp[:-1], jnp.asarray(toks[1:, None]), axis=-1)[..., 0]
    )
    np.testing.assert_allclose(per_sample[i][: T - 1], want, atol=2e-3)


def test_generate_smoke():
    cfg = tiny_config(vocab_size=32)
    params = transformer.init_params(cfg, jax.random.PRNGKey(2))
    eng = JaxTrainEngine(cfg, params, compute_dtype="float32")
    prompts = np.array([3, 4, 5, 6, 7, 8], np.int32)
    s = SequenceSample.from_default(
        ids=["p0", "p1"],
        data={"packed_prompts": prompts},
        seqlens=[2, 4],
    )
    out = eng.generate(
        s, MicroBatchSpec(),
        GenerationHyperparameters(max_new_tokens=8, greedy=True, n=2),
        key=jax.random.PRNGKey(0), eos_token_id=1, pad_token_id=0,
    )
    assert out["output_ids"].shape == (4, 8)  # 2 prompts × n=2
    assert (out["output_lens"] >= 0).all() and (out["output_lens"] <= 8).all()
