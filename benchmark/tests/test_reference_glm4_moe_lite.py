"""``reference_glm4_moe_lite`` alone, on the CPU at a toy size: the
properties its equations have whatever implements them (by hand:
``JAX_PLATFORMS=cpu python -m pytest
benchmark/tests/test_reference_glm4_moe_lite.py -q``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_glm4_moe_lite as ref

CFG = {
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "hidden_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 12,
    "kv_lora_rank": 8, "qk_nope_head_dim": 12, "qk_rope_head_dim": 4,
    "v_head_dim": 16, "intermediate_size": 48, "moe_intermediate_size": 24,
    "vocab_size": 67, "rms_norm_eps": 1e-5, "rope_theta": 1000000,
    "n_routed_experts": 8, "n_shared_experts": 1, "num_experts_per_tok": 3,
    "norm_topk_prob": True, "routed_scaling_factor": 1.8,
}
H, NOPE, DR, DV, R, D = 4, 12, 4, 16, 8, 32


def weights(seed=0, held=8, scale=0.3):
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 64))

    def nrm(*shape):
        return jax.random.normal(next(ks), shape) * scale

    def attn(n):
        return {"ln1": 1 + 0.1 * nrm(n, D) / scale, "ln2": jnp.ones((n, D)),
                "wq_a": nrm(n, D, 12), "q_a_norm": jnp.ones((n, 12)),
                "wq_b": nrm(n, 12, H * (NOPE + DR)),
                "wkv_a": nrm(n, D, R + DR), "kv_a_norm": jnp.ones((n, R)),
                "wkv_b": nrm(n, R, H * (NOPE + DV)), "wo": nrm(n, H * DV, D)}

    dense = {**attn(1), "w_gate": nrm(1, D, 48), "w_up": nrm(1, D, 48),
             "w_down": nrm(1, 48, D)}
    full = {**attn(2), "router": nrm(2, D, 8),
            "router_bias": 0.1 * nrm(2, 8) / scale,
            "e_gate": nrm(2, held, D, 24), "e_up": nrm(2, held, D, 24),
            "e_down": nrm(2, held, 24, D), "s_gate": nrm(2, D, 24),
            "s_up": nrm(2, D, 24), "s_down": nrm(2, 24, D)}
    return {"embedding": nrm(67, D) / scale, "final_ln": jnp.ones(D),
            "lm_head": nrm(D, 67),
            "layers": {"full_dense": dense, "full": full}}


def tokens(n=23, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(2, 67, n),
                       jnp.int32)


def layer(params, kind="full", i=0):
    return {k: w[i] for k, w in params["layers"][kind].items()}


def test_shapes_and_a_normalised_distribution():
    p = weights()
    lg = ref.logits(p, CFG, tokens())
    assert lg.shape == (23, 67) and bool(jnp.isfinite(lg).all())
    lp = ref.token_logprobs(p, CFG, tokens())
    assert lp.shape == (22,) and bool((lp < 0).all())
    assert [d for d, _ in ref.layers_of(p, CFG)] == [True, False, False]


def test_the_model_is_causal_and_rope_is_relative():
    p = weights()
    t = tokens()
    a = ref.logits(p, CFG, t)
    b = ref.logits(p, CFG, t.at[15].set(5))
    np.testing.assert_allclose(a[:15], b[:15], atol=1e-5)
    assert float(jnp.abs(a[15:] - b[15:]).max()) > 1e-3
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 1, 2, DR))
    q, k = (ref.rope(jnp.tile(v, (9, 1, 1)), 10.0) for v in x)
    # a dot product of two turned vectors depends on their distance only
    np.testing.assert_allclose(jnp.sum(q[5] * k[2]), jnp.sum(q[7] * k[4]),
                               rtol=1e-4)
    assert abs(float(jnp.sum(q[5] * k[2]) - jnp.sum(q[5] * k[4]))) > 1e-3
    np.testing.assert_allclose(q[0], x[0, 0], atol=1e-6)


def test_the_rotary_key_is_one_vector_a_token_for_every_head():
    p = weights()
    lp = layer(p, "full_dense")
    u = jax.random.normal(jax.random.PRNGKey(2), (11, D))
    q, k, v = ref.qkv(u, CFG, lp)
    assert q.shape == k.shape == (11, H, NOPE + DR) and v.shape == (11, H, DV)
    for h in range(1, H):
        np.testing.assert_array_equal(k[:, h, NOPE:], k[:, 0, NOPE:])
        assert float(jnp.abs(k[:, h, :NOPE] - k[:, 0, :NOPE]).max()) > 1e-2
    # token 0 sits at position 0: its rotary parts are not turned
    ckv = ref.mm(u, lp["wkv_a"])
    np.testing.assert_allclose(k[0, 0, NOPE:], ckv[0, R:], atol=1e-6)
    # the k/v latent's norm spans the first 8 ONLY: scaling k_r's columns
    # of the down-projection leaves k_nope and v alone
    scaled = {**lp, "wkv_a": lp["wkv_a"].at[:, R:].multiply(3.0)}
    _, k3, v3 = ref.qkv(u, CFG, scaled)
    np.testing.assert_allclose(k3[..., :NOPE], k[..., :NOPE], atol=1e-6)
    np.testing.assert_allclose(v3, v, atol=1e-6)
    np.testing.assert_allclose(k3[0, :, NOPE:], 3 * k[0, :, NOPE:],
                               atol=1e-5)


def test_the_gates_the_factor_and_the_shared_expert():
    p = weights()
    lp = layer(p)
    x = jax.random.normal(jax.random.PRNGKey(3), (19, D))
    g = ref.gates(x, CFG, lp)
    assert bool(((g > 0).sum(-1) == 3).all())
    np.testing.assert_allclose(g.sum(-1), 1.8, atol=1e-5)
    scores, idx = ref.chosen(x, CFG, lp)
    by = scores + lp["router_bias"]
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(np.argsort(
        -np.asarray(by), -1)[:, :3], -1))
    shared = ref.swiglu(x, lp["s_gate"], lp["s_up"], lp["s_down"])
    routed = ref.moe(x, CFG, lp, frozenset({"no_shared_expert"}))
    np.testing.assert_allclose(ref.moe(x, CFG, lp), routed + shared,
                               atol=1e-5)
    plain = ref.moe(x, {**CFG, "routed_scaling_factor": 1.0}, lp)
    np.testing.assert_allclose(ref.moe(x, CFG, lp) - shared,
                               1.8 * (plain - shared), atol=1e-4)


def test_the_shares_add_up_with_the_shared_expert_counted_once():
    p = weights()
    lp = layer(p)
    x = jax.random.normal(jax.random.PRNGKey(4), (13, D))
    shared = ref.swiglu(x, lp["s_gate"], lp["s_up"], lp["s_down"])
    parts = []
    for shard in range(4):
        keys = {**CFG, "n_routed_experts": 2, "num_routed_experts": 8,
                "expert_shard_index": shard}
        held = {**lp, **{k: lp[k][2 * shard:2 * shard + 2]
                         for k in ("e_gate", "e_up", "e_down")}}
        parts.append(ref.moe(x, keys, held) - shared)
    np.testing.assert_allclose(sum(parts) + shared, ref.moe(x, CFG, lp),
                               atol=1e-4)


@pytest.mark.parametrize("which", ref.WRONG)
def test_every_wrong_model_differs(which):
    p = weights()
    t = tokens()
    a = ref.token_logprobs(p, CFG, t)
    b = ref.token_logprobs(p, CFG, t, frozenset({which}))
    assert float(jnp.abs(a - b).max()) > 1e-3, which


def test_the_losses_have_gradients_and_the_bias_none():
    p = weights()
    t = tokens(13)
    g = jax.grad(ref.loss)(p, CFG, t)
    flat = jax.tree.leaves_with_path(g)
    assert all(bool(jnp.isfinite(x).all()) for _, x in flat)
    assert not np.any(g["layers"]["full"]["router_bias"])
    assert float(jnp.abs(g["layers"]["full"]["wkv_a"][..., R:]).max()) > 0
    old = ref.token_logprobs(p, CFG, t)
    adv = jax.random.normal(jax.random.PRNGKey(5), (12,))
    mask = (jnp.arange(12) >= 4).astype(jnp.float32)
    got = jax.grad(ref.ppo_loss)(p, CFG, t, old, adv, mask)
    want = jax.grad(ref.loss)(p, CFG, t, adv * mask / mask.sum())
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5 * (
            1 + float(jnp.abs(b).max())))
