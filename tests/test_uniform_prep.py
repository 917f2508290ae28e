"""Device-side advantage prep (make_advantage_prep over an uploaded
UniformBatch) must match the host path (compute_advantages_and_returns +
normalize_advantages) exactly, and a train step must take the same
optimizer step whichever side computed the advantages."""

import copy
import dataclasses

import numpy as np
import pytest

import jax

from areal_tpu.algorithms.ppo import (
    PPOActorInterface,
    PPOHyperparameters,
    attach_keys,
    compute_advantages_and_returns,
    normalize_advantages,
)
from areal_tpu.api.data import MicroBatchSpec, SequenceSample
from areal_tpu.api.model import FinetuneSpec, Model
from areal_tpu.backend import microbatch as mbu
from areal_tpu.backend.jax_train import JaxTrainBackend, OptimizerConfig
from areal_tpu.models import transformer
from areal_tpu.models.config import tiny_config


def _make_batch(n_seq=9, vocab=128, seed=0, with_values=False, with_ref=True):
    rng = np.random.RandomState(seed)
    plens = rng.randint(2, 6, n_seq)
    glens = rng.randint(4, 12, n_seq)
    seqlens = (plens + glens).astype(int)
    total = int(seqlens.sum())
    pmask = np.concatenate([
        np.concatenate([np.ones(p, np.int32), np.zeros(g, np.int32)])
        for p, g in zip(plens, glens)
    ])
    data = {
        "packed_input_ids": rng.randint(2, vocab, total).astype(np.int32),
        "prompt_mask": pmask,
        "packed_logprobs": np.where(
            pmask == 0, -rng.rand(total), 0.0).astype(np.float32),
        "rewards": rng.randn(n_seq).astype(np.float32),
        "seq_no_eos_mask": (rng.rand(n_seq) < 0.3).astype(np.float32),
    }
    if with_ref:
        data["packed_ref_logprobs"] = np.where(
            pmask == 0, -rng.rand(total), 0.0).astype(np.float32)
    if with_values:
        data["values"] = rng.randn(total).astype(np.float32)
    return SequenceSample.from_default(
        ids=[f"s{i}" for i in range(n_seq)],
        data=data,
        seqlens=seqlens.tolist(),
    )


def _engine(vocab=128, seed=0):
    cfg = tiny_config(vocab_size=vocab)
    params = transformer.init_params(cfg, jax.random.PRNGKey(seed))
    model = Model("actor", (cfg, params), tokenizer=None)
    backend = JaxTrainBackend(
        optimizer=OptimizerConfig(lr=1e-3, lr_scheduler_type="constant"),
        compute_dtype="float32", length_bucket=16, rows_bucket=2,
        seqs_bucket=4,
    )
    return backend.initialize(model, FinetuneSpec(1, 8, 4))


@pytest.mark.parametrize("kl_coef", [0.0, 0.1])
@pytest.mark.parametrize("with_values", [False, True])
def test_device_prep_matches_host_path(kl_coef, with_values):
    hp = PPOHyperparameters(adv_norm=True, kl_ctl=kl_coef,
                            disable_value=not with_values)
    batch = _make_batch(with_values=with_values)
    # Host path.
    extra = compute_advantages_and_returns(batch, hp, kl_coef)
    host_kl = extra.pop("_mean_kl")
    host = attach_keys(batch, extra)
    normalize_advantages(host, hp)

    # Device path on an uploaded uniform batch.
    model = _engine()
    eng = model.module
    iface = PPOActorInterface(hp)
    ub = eng.upload_uniform(batch, MicroBatchSpec(max_tokens_per_mb=64))
    scalars = eng.run_prep(
        ub, iface._prep_fn, iface._prep_fn, scalars={"kl_coef": kl_coef}
    )
    assert float(scalars["_mean_kl"]) == pytest.approx(host_kl, abs=1e-5)

    # Scatter device grids back into packed order and compare.
    adv_grid = np.asarray(ub.grids["advantages"])
    per_mb = [
        adv_grid[i * ub.R : (i + 1) * ub.R] for i in range(ub.n_mbs)
    ]
    packed = np.concatenate(
        mbu.scatter_back(ub.mbs, per_mb, batch.bs)
    )
    np.testing.assert_allclose(
        packed, host.data["advantages"], atol=1e-4, rtol=1e-4
    )


def test_train_step_device_prep_matches_host_advantages():
    """The device advantage prep and the host-side advantage branch
    (``group_adv_norm``) must produce the same updated parameters for the
    same inputs (same grads → same adamw step)."""
    # One minibatch: with k>1 the two branches partition differently
    # (token-balanced vs contiguous-rows), which is a legitimate semantic
    # difference; with k=1 both take one step over identical data. One
    # group holding every sequence: whitening per group IS global whitening.
    hp = PPOHyperparameters(ppo_n_minibatches=1, adv_norm=True, kl_ctl=0.0,
                            disable_value=True)
    batch = _make_batch()
    spec = MicroBatchSpec(max_tokens_per_mb=64)

    m1 = _engine()
    i1 = PPOActorInterface(copy.deepcopy(hp))
    s1 = i1.train_step(m1, batch, spec)  # advantages on device (run_prep)

    m2 = _engine()
    i2 = PPOActorInterface(dataclasses.replace(hp, group_adv_norm=True))
    batch.metadata["group"] = ["g"] * batch.bs
    s2 = i2.train_step(m2, batch, spec)  # advantages on the host

    for a, b in zip(
        jax.tree_util.tree_leaves(m1.module.params),
        jax.tree_util.tree_leaves(m2.module.params),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-6, rtol=2e-5
        )
    assert s1["mean_kl"] == pytest.approx(s2["mean_kl"], abs=1e-6)
    assert s1["n_action_tokens"] == s2["n_action_tokens"]


def test_fast_path_takes_n_minibatch_steps():
    """Advisor r3 (high): with the default MicroBatchSpec the packer puts
    the whole batch in one uniform micro-batch, which silently collapsed
    ppo_n_minibatches optimizer steps into one. The fast path must request
    at least ppo_n_minibatches micro-batches from the packer."""
    hp = PPOHyperparameters(ppo_n_minibatches=4, adv_norm=True, kl_ctl=0.0,
                            disable_value=True)
    batch = _make_batch(n_seq=16)
    model = _engine()
    iface = PPOActorInterface(hp)
    stats = iface.train_step(model, batch, MicroBatchSpec())
    assert stats["n_ppo_steps"] == 4.0
