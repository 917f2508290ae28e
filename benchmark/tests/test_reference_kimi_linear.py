"""``reference_kimi_linear`` alone, on the CPU at a toy size: the properties
its equations have whatever implements them (by hand: ``JAX_PLATFORMS=cpu
python -m pytest benchmark/tests/test_reference_kimi_linear.py -q``), and
``kda_cost`` / ``kimi_trace`` on records made by hand."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import kda_cost, kimi_trace
from benchmark import reference_kimi_linear as ref

CFG = {
    "num_hidden_layers": 4, "held_layers": [1, 6, 7, 8],
    "first_k_dense_replace": 1, "hidden_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 4, "q_lora_rank": None, "kv_lora_rank": 8,
    "qk_nope_head_dim": 12, "qk_rope_head_dim": 4, "v_head_dim": 8,
    "linear_attn_config": {
        "kda_layers": [1, 2, 3, 5, 6, 7], "full_attn_layers": [4, 8],
        "num_heads": 4, "head_dim": 8, "short_conv_kernel_size": 4},
    "intermediate_size": 48, "moe_intermediate_size": 24, "vocab_size": 67,
    "rms_norm_eps": 1e-5, "rope_theta": 10000, "num_experts": 8,
    "num_shared_experts": 1, "num_experts_per_token": 3,
    "moe_renormalize": True, "routed_scaling_factor": 2.446,
}
H, DH, NOPE, DR, DV, R, D = 4, 8, 12, 4, 8, 8, 32


def weights(seed=0, held=8, scale=0.3):
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 96))

    def nrm(*shape):
        return jax.random.normal(next(ks), shape) * scale

    def norms(n):
        return {"ln1": 1 + 0.1 * nrm(n, D) / scale, "ln2": jnp.ones((n, D))}

    def mixer(n):
        return {**norms(n), "kda_qkv": nrm(n, D, 3 * H * DH),
                "kda_conv": nrm(n, 4, 3 * H * DH),
                "kda_gates_a": nrm(n, D, H + 2 * DH),
                "kda_f_b": nrm(n, DH, H * DH), "kda_g_b": nrm(n, DH, H * DH),
                "kda_A_log": jnp.log(1 + jnp.abs(nrm(n, H)) / scale),
                "kda_dt_bias": nrm(n, H * DH) / scale - 2.0,
                "kda_norm": jnp.ones((n, DH)), "kda_out": nrm(n, H * DH, D)}

    def experts(n):
        return {"router": nrm(n, D, 8), "router_bias": 0.1 * nrm(n, 8) / scale,
                "e_gate": nrm(n, held, D, 24), "e_up": nrm(n, held, D, 24),
                "e_down": nrm(n, held, 24, D), "s_gate": nrm(n, D, 24),
                "s_up": nrm(n, D, 24), "s_down": nrm(n, 24, D)}

    attn = {**norms(1), "wq": nrm(1, D, H * (NOPE + DR)),
            "wkv_a": nrm(1, D, R + DR), "kv_a_norm": jnp.ones((1, R)),
            "wkv_b": nrm(1, R, H * (NOPE + DV)), "wo": nrm(1, H * DV, D)}
    return {"embedding": nrm(67, D) / scale, "final_ln": jnp.ones(D),
            "lm_head": nrm(D, 67),
            "layers": {"kda_dense": {**mixer(1), "w_gate": nrm(1, D, 48),
                                     "w_up": nrm(1, D, 48),
                                     "w_down": nrm(1, 48, D)},
                       "kda": {**mixer(2), **experts(2)},
                       "full": {**attn, **experts(1)}}}


def tokens(n=23, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(2, 67, n),
                       jnp.int32)


def layer(params, kind="kda", i=0):
    return {k: w[i] for k, w in params["layers"][kind].items()}


def test_shapes_and_a_normalised_distribution():
    p = weights()
    lg = ref.logits(p, CFG, tokens())
    assert lg.shape == (23, 67) and bool(jnp.isfinite(lg).all())
    lp = ref.token_logprobs(p, CFG, tokens())
    assert lp.shape == (22,) and bool((lp <= 0).all())
    assert [(k, d) for k, d, _ in ref.layers_of(p, CFG)] == [
        (True, True), (True, False), (True, False), (False, False)]


def test_the_model_is_causal_and_has_no_position():
    """A later token moves no earlier logit; and the attention block has
    no position embedding: the branch of a document's LAST token is the
    same wherever the earlier tokens stand (a softmax over a set)."""
    p = weights()
    a, b = tokens(), tokens().at[17].set(5)
    la, lb = ref.logits(p, CFG, a), ref.logits(p, CFG, b)
    np.testing.assert_allclose(la[:17], lb[:17], atol=1e-6)
    assert float(jnp.abs(la[17:] - lb[17:]).max()) > 1e-3
    u = jax.random.normal(jax.random.PRNGKey(1), (12, D))
    perm = jnp.concatenate([jnp.arange(11)[::-1], jnp.asarray([11])])
    lp = layer(p, "full")
    np.testing.assert_allclose(ref.attention(u, CFG, lp)[-1],
                               ref.attention(u[perm], CFG, lp)[-1], atol=2e-6)


def test_the_decay_is_a_channels_and_the_state_is_the_recurrences():
    """The rule against the closed form of its first two tokens, and a
    decay that differs by channel against its head's mean."""
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    q, k = (ref.l2(jax.random.normal(ks[i], (5, 2, 4))) for i in (0, 1))
    v = jax.random.normal(ks[2], (5, 2, 3))
    g = -jnp.abs(jax.random.normal(ks[3], (5, 2, 4)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (5, 2)))
    o = ref.delta_rule(q, k, v, g, beta)
    # t = 0: S = k0 (β0 v0)ᵀ;  o0 = (k0·q0) β0 v0
    np.testing.assert_allclose(
        o[0], jnp.sum(k[0] * q[0], -1)[:, None] * beta[0][:, None] * v[0],
        atol=1e-6)
    # t = 1: the state decayed a channel, then the delta
    S = jnp.exp(g[1])[:, :, None] * (
        k[0][:, :, None] * (beta[0][:, None] * v[0])[:, None, :])
    d = beta[1][:, None] * (v[1] - jnp.einsum("hkv,hk->hv", S, k[1]))
    S = S + k[1][:, :, None] * d[:, None, :]
    np.testing.assert_allclose(o[1], jnp.einsum("hkv,hk->hv", S, q[1]),
                               atol=1e-6)
    flat = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
    assert float(jnp.abs(ref.delta_rule(q, k, v, flat, beta) - o).max()) > 1e-3


def test_the_gates_the_factor_and_the_shared_expert():
    p = weights()
    lp = layer(p)
    x = jax.random.normal(jax.random.PRNGKey(3), (9, D))
    g = ref.gates(x, CFG, lp)
    assert g.shape == (9, 8) and bool(((g > 0).sum(-1) == 3).all())
    np.testing.assert_allclose(g.sum(-1), 2.446, rtol=1e-6)
    scores, idx = ref.chosen(x, CFG, lp)
    by = scores + lp["router_bias"]
    assert bool((jnp.sort(idx, -1) == jnp.sort(
        jnp.argsort(-by, -1)[:, :3], -1)).all())
    shared = ref.swiglu(x, lp["s_gate"], lp["s_up"], lp["s_down"])
    np.testing.assert_allclose(ref.moe(x, CFG, lp),
                               ref.routed(x, CFG, lp) + shared, atol=1e-6)


def test_the_shares_add_up_with_the_shared_expert_counted_once():
    p = weights()
    lp = layer(p)
    x = jax.random.normal(jax.random.PRNGKey(4), (11, D))
    whole = ref.moe(x, CFG, lp)
    shared = ref.swiglu(x, lp["s_gate"], lp["s_up"], lp["s_down"])
    parts = 0
    for shard in range(4):
        keys = {**CFG, "num_experts": 2, "num_routed_experts": 8,
                "expert_shard_index": shard}
        held = {**lp, **{k: lp[k][2 * shard:2 * shard + 2]
                         for k in ("e_gate", "e_up", "e_down")}}
        parts = parts + ref.routed(x, keys, held)
    np.testing.assert_allclose(parts + shared, whole, atol=1e-5)


@pytest.mark.parametrize("which", ref.WRONG)
def test_every_wrong_model_differs(which):
    p = weights()
    n = 150 if which == "state_bf16_each_chunk" else 23
    right = ref.token_logprobs(p, CFG, tokens(n))
    wrong = ref.token_logprobs(p, CFG, tokens(n), frozenset({which}))
    assert float(jnp.abs(wrong - right).max()) > 1e-5, which


def test_the_losses_have_gradients_and_the_bias_none():
    p = weights()
    g = jax.grad(ref.loss)(p, CFG, tokens())
    assert all(bool(jnp.isfinite(x).all()) for x in jax.tree.leaves(g))
    assert not np.any(g["layers"]["kda"]["router_bias"])
    assert np.any(g["layers"]["kda"]["kda_A_log"])
    assert np.any(g["layers"]["kda_dense"]["kda_dt_bias"])


def test_the_costs_are_the_algorithms():
    """A rule's operations at a decay a channel are Gated DeltaNet's at as
    many key heads as value heads; its bytes hold g at the key's size in
    float32. Attention at 192 / 128 is the mean of the two widths."""
    from benchmark import gdn_cost, peaks

    ops, nbytes = kda_cost.kda_rule_cost(1, 8192, 32, 128, 128, False)
    gops, gbytes = gdn_cost.gdn_rule_cost(1, 8192, 32, 32, 128, 128, False)
    assert ops == gops
    assert nbytes - gbytes == 4 * 8192 * 32 * 128 - 4 * 8192 * 32
    assert kda_cost.kda_rule_cost(1, 8192, 32, 128, 128, True)[0] == 2 * ops
    cfg = {**CFG, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
           "v_head_dim": 128, "num_attention_heads": 32,
           "num_key_value_heads": 32}
    got = kda_cost.attention_cost(cfg, [1000, 3000], False)
    want = [sum(peaks.flash_attention_cost(1, n, 32, 32, 160, False)[i]
                for n in (1000, 3000)) for i in (0, 1)]
    np.testing.assert_allclose(got, want, rtol=1e-12)
    assert kda_cost.layer_counts(CFG) == {"kda": 3, "attn": 1, "dense": 1,
                                          "experts": 3}
    assert (kda_cost.runs(CFG, True), kda_cost.runs(CFG, False)) == (2, 1)


def test_the_readers_return_nothing_where_there_is_nothing_to_read():
    """On the parent commit's records (no KDA scope, no counter) every
    reader gives None and raises nothing."""
    records = {"trace": None, "counters": {}, "device": {"kind": "TPU v5e"},
               "config": CFG}
    for read in (kimi_trace.rule_roofline, kimi_trace.attn_roofline,
                 kimi_trace.experts_roofline, kimi_trace.mla_proj_busy_pct,
                 kimi_trace.attn_busy_pct, kimi_trace.resets_in_chunk_per_row,
                 lambda r: kimi_trace.scope_busy_pct(r, "kda_rule")):
        assert read(records) is None
    assert kimi_trace.resets_in_chunk_per_row(
        {"counters": {"kda_resets_in_chunk_per_row": 0.75}}) == 0.75


def test_the_attention_kernels_time_counts_a_call_batched_over_rows():
    """A grid of several rows reaches the trace as ``closed_call.N`` under
    the kernel's scope: counted with the ``splash_*`` ops, and no other
    scope's ``closed_call`` nor the scope's layout glue with them."""
    from benchmark import gdn_trace
    from benchmark import program_trace as pt

    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": pt.MODULES_LINE, "events": [(0.0, 10.0, "jit_grad(7)")]},
        {"name": "XLA Ops", "events": [
            (0.0, 1.0, "splash_mqa_fwd_segmented_residuals.7"),
            (1.0, 3.0, "closed_call.146"), (3.0, 4.0, "closed_call.9"),
            (4.0, 6.0, "fusion.3")]}]}]
    names = {
        ("7", "splash_mqa_fwd_segmented_residuals.7"):
            "jit(grad)/attention/causal_attention/vmap(pallas_call)",
        ("7", "closed_call.146"):
            "jit(grad)/transpose(jvp(attention))/"
            "transpose(jvp(causal_attention))/closed_call",
        ("7", "closed_call.9"): "jit(grad)/kda_rule/closed_call",
        ("7", "fusion.3"): "jit(grad)/attention/causal_attention/transpose"}
    kernels = {k: v for k, v in names.items()
               if kimi_trace.ATTN_KERNEL_OP.match(k[1])}
    red = gdn_trace.reduce_planes(planes, kernels, (kimi_trace.ATTN_SCOPE,))
    assert red["scopes"] == {kimi_trace.ATTN_SCOPE: 3.0}
