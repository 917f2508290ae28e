"""Qwen3-Next (``model_type`` qwen3_next) through the system against the
benchmark's plain reference (``benchmark/reference_qwen3_next.py``:
float32, the gated delta rule a token at a time, attention as a masked
softmax, every held expert on every token, one document at a time) on
seeded weights, on the CPU at a tiny size: hidden 64, 4 query / 2
key-value heads of 32 with RoPE on the first 8 dims, a Gated DeltaNet
mixer of 2 key / 4 value heads of 16, 8 experts of 32 (3 a token) beside a
gated shared expert of 32, the published period ``L L L F``.

Both sides compute in float32 here, so they differ by the order of
float32 sums only; every fault ``reference.WRONG`` names moves logits by
1e-2 and more.
"""

import dataclasses
import functools
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import gdn, hf, moe, transformer
from areal_tpu.models.config import FULL, GDN
from benchmark import reference_qwen3_next as ref

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HF_KEYS = {
    "model_type": "qwen3_next", "num_hidden_layers": 4,
    "full_attention_interval": 4, "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "partial_rotary_factor": 0.25, "rope_theta": 10000000,
    "rope_scaling": None, "attention_bias": False,
    "intermediate_size": 96, "vocab_size": 97, "hidden_act": "silu",
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 16, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "decoder_sparse_step": 1,
    "mlp_only_layers": [], "num_experts": 8, "num_experts_per_tok": 3,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "norm_topk_prob": True, "router_aux_loss_coef": 0.001,
    "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
    "use_sliding_window": False, "max_position_embeddings": 4096,
}
TOL = dict(atol=3e-4, rtol=3e-4)
ZERO_CENTRED = ("ln1", "ln2", "final_ln", "q_norm", "k_norm")
AS_DRAWN = ("gdn_conv", "gdn_dt_bias")


def model(keys=HF_KEYS, seed=0, scale=0.3, chunk=16):
    """(config, float32 params): init_params with the matrices scaled up
    (so that every mixer and expert matters), the norm weights random
    (around 0 where zero-centred, around 1 for the gated norm), ``A_log``
    drawn low so that the state remembers across documents' lengths, the
    rule in chunks of ``chunk``."""
    cfg = hf.config_from_hf(types.SimpleNamespace(**keys))
    cfg = dataclasses.replace(
        cfg, gdn=dataclasses.replace(cfg.gdn, chunk_size=chunk))
    flat = hf.flatten_pytree(
        transformer.init_params(cfg, jax.random.PRNGKey(seed)))
    rngs = jax.random.split(jax.random.PRNGKey(seed + 1), len(flat))
    for (name, x), k in zip(sorted(flat.items()), rngs):
        leaf = name.split("/")[-1]
        if leaf in ZERO_CENTRED:
            flat[name] = 0.1 * jax.random.normal(k, x.shape)
        elif leaf == "gdn_norm":
            flat[name] = 1.0 + 0.1 * jax.random.normal(k, x.shape)
        elif leaf == "gdn_A_log":
            flat[name] = jnp.log(jax.random.uniform(
                k, x.shape, minval=0.02, maxval=0.5))
        elif leaf == "embedding":
            flat[name] = x * 40.0
        elif leaf not in AS_DRAWN:
            flat[name] = x * (scale / 0.02)
    return cfg, hf.unflatten_pytree(flat)


def tokens(seed=0, T=43):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(2, HF_KEYS["vocab_size"], T), jnp.int32)


def packed_row(lens, T, seed=10):
    """(row [1, T], segment ids, positions, the documents) of documents of
    ``lens`` tokens packed one behind another, then padding."""
    docs = [tokens(seed + i, n) for i, n in enumerate(lens)]
    pad = T - sum(lens)
    row = jnp.concatenate(docs + [jnp.zeros(pad, jnp.int32)])[None]
    seg = jnp.asarray([sum(([i + 1] * n for i, n in enumerate(lens)), [])
                       + [0] * pad], jnp.int32)
    pos = jnp.asarray([sum((list(range(n)) for n in lens), []) + [0] * pad],
                      jnp.int32)
    return row, seg, pos, docs


@functools.partial(jax.jit, static_argnames=("cfg", "remat"))
def system_logits(params, cfg, tok, seg=None, pos=None, remat=False):
    """Logits of a packed grid ``tok`` [B, T] (or one document [T])."""
    one = tok.ndim == 1
    if one:
        tok = tok[None]
    B, T = tok.shape
    seg = jnp.ones((B, T), jnp.int32) if seg is None else seg
    pos = jnp.broadcast_to(jnp.arange(T), (B, T)) if pos is None else pos
    out, _ = transformer.forward(
        params, cfg, tok, pos, segment_ids=seg, attn_impl="reference",
        return_kv=False, remat=remat)
    return out[0] if one else out


@functools.partial(jax.jit, static_argnames=("cfg", "remat"))
def system_loss_and_grad(params, cfg, tok, remat=False):
    return jax.value_and_grad(lambda p: -jnp.mean(ref.logprobs_of(
        system_logits(p, cfg, tok, remat=remat), tok)))(params)


@functools.partial(jax.jit, static_argnames=("wrong",))
def ref_logprobs(params, tok, wrong=ref.NONE):
    return ref.token_logprobs(params, HF_KEYS, tok, wrong)


ref_logits = jax.jit(lambda params, tok: ref.logits(params, HF_KEYS, tok))
ref_loss_and_grad = jax.jit(jax.value_and_grad(
    lambda params, tok, w=None: ref.loss(params, HF_KEYS, tok, w)))
GRAD_TOKENS = dict(seed=1, T=29)


# ---- (a) the family ----

def test_the_family_reads_the_blocks():
    cfg, params = model()
    assert cfg.layer_kinds == (GDN, GDN, GDN, FULL)
    assert transformer.period_runs(cfg.period_kinds) == (
        ((GDN,), 3), ((FULL,), 1))
    assert cfg.is_hybrid and cfg.has_cacheless_layers
    assert cfg.zero_centered_norm and cfg.gated_attention
    assert cfg.rotary_dim == 8 and cfg.moe.shared_expert_gate
    assert cfg.block_counts() == {"gdn/experts": 3, "full/experts": 1}
    assert set(params["layers"]) == {GDN, FULL}
    assert "wg" in params["layers"][FULL] and "wg" not in params["layers"][GDN]
    assert "q_norm" not in params["layers"][GDN]
    assert params["layers"][GDN]["s_sig"].shape == (3, 64, 1)
    # a cut that starts inside the published stack keeps the period
    late = hf.config_from_hf(types.SimpleNamespace(
        **{**HF_KEYS, "num_hidden_layers": 2, "first_layer_index": 2}))
    assert late.layer_kinds == (GDN, FULL)


def test_the_config_goes_out_and_comes_back():
    cfg, _ = model(chunk=64)
    share = {**HF_KEYS, "num_experts": 2, "num_routed_experts": 8,
             "expert_shard_count": 4, "expert_shard_index": 3}
    for c in (cfg, hf.config_from_hf(types.SimpleNamespace(**share))):
        back = hf.config_from_hf(types.SimpleNamespace(**hf.hf_config_dict(c)))
        assert back == c


@pytest.mark.parametrize("key,value", [
    ("mlp_only_layers", [0]), ("decoder_sparse_step", 2),
    ("rope_scaling", {"rope_type": "yarn", "factor": 4.0}),
    ("attention_bias", True), ("use_sliding_window", True),
    ("hidden_act", "gelu")])
def test_keys_of_the_family_that_are_not_built_are_refused_by_name(key, value):
    name = next(why for k, _, why in hf.QWEN3_NEXT_REFUSALS
                if k == key).split(":")[0]
    with pytest.raises(NotImplementedError, match=name):
        hf.config_from_hf(types.SimpleNamespace(**{**HF_KEYS, key: value}))


def test_parameter_count_at_the_published_widths():
    """``param_count`` equals the leaves' sizes and the number in the
    configuration file, at the published widths (shapes only)."""
    from benchmark import gdn_cost, weights

    with open(os.path.join(
            REPO, "benchmark/configs/qwen3-next-80b-a3b.json")) as f:
        cfg_file = json.load(f)
    cfg = weights.model_config(cfg_file)
    shapes = jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert n == transformer.param_count(cfg) == cfg_file["n_parameters"]
    by_kind = {kind: sum(int(np.prod(x.shape[1:])) for x in
                         jax.tree.leaves(shapes["layers"][kind]))
               for kind in (GDN, FULL)}
    held = 16 * 3 * 2048 * 512
    assert by_kind[GDN] - held == 37_918_912  # ISSUE 52's reckoning
    assert by_kind[FULL] - held == 31_463_936
    assert gdn.gdn_param_count(cfg.gdn, 2048) == 33_718_464
    assert cfg.gdn.chunk_size == gdn_cost.CHUNK
    # no width differs from the published one
    for key, published in cfg_file["reduced_from"].items():
        assert key in cfg_file["reduced"] and cfg_file[key] < published
    assert transformer.activated_param_count(cfg) < n
    assert 0 < gdn_cost.share_params(cfg_file) < n


# ---- (b) the model against the reference ----

def test_logprobs_match_the_reference():
    cfg, params = model()
    tok = tokens(T=43)
    got = ref.logprobs_of(system_logits(params, cfg, tok), tok)
    np.testing.assert_allclose(got, ref_logprobs(params, tok), **TOL)


@pytest.mark.parametrize("remat", ["full", "matmuls"])
def test_loss_and_every_gradient_match_the_reference(remat):
    """Through the scanned runs (``L L L`` is one run, scanned three
    times), whatever the backward finds kept."""
    cfg, params = model()
    tok = tokens(**GRAD_TOKENS)
    l, g = system_loss_and_grad(params, cfg, tok, remat=remat)
    l_ref, g_ref = ref_loss_and_grad(params, tok)
    np.testing.assert_allclose(l, l_ref, rtol=1e-5)
    flat, flat_ref = hf.flatten_pytree(g), hf.flatten_pytree(g_ref)
    assert set(flat) == set(flat_ref)
    for name in sorted(flat):
        scale = float(jnp.abs(flat_ref[name]).max())
        assert scale > 0, name  # every leaf is reached
        np.testing.assert_allclose(
            flat[name], flat_ref[name], atol=2e-3 * scale, rtol=2e-3,
            err_msg=name)


def test_the_ppo_loss_of_the_reference_has_the_surrogates_gradient():
    """At ratio 1 the clipped surrogate's gradient is that of
    ``-mean(A · logprob)`` over the masked tokens."""
    _, params = model()
    tok = tokens(**GRAD_TOKENS)
    n = len(tok) - 1
    old = ref_logprobs(params, tok)
    adv = jnp.asarray(np.random.default_rng(0).normal(size=n), jnp.float32)
    mask = jnp.asarray([0] * 8 + [1] * (n - 8), jnp.float32)
    g = jax.jit(jax.grad(lambda p: ref.ppo_loss(
        p, HF_KEYS, tok, old, adv, mask)))(params)
    _, g_ref = ref_loss_and_grad(params, tok, adv * mask / (n - 8))
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-4)


@pytest.mark.parametrize("which", ref.WRONG)
def test_a_wrong_reference_is_told_apart(which):
    cfg, params = model()
    tok = tokens(3, T=43)
    got = ref.logprobs_of(system_logits(params, cfg, tok), tok)
    wrong = ref_logprobs(params, tok, frozenset({which}))
    if which == "state_in_bfloat16":  # the logprobs are nearly blind to it
        assert float(jnp.abs(got - wrong).max()) > 1e-4
    else:
        assert float(jnp.abs(got - wrong).max()) > 1e-2, which


# ---- (c) the rule ----

def rule_inputs(B=2, T=96, G=2, H=4, dk=16, dv=16, seed=2):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = gdn.l2_normalize(jax.random.normal(ks[0], (B, T, G, dk))) * dk ** -0.5
    k = gdn.l2_normalize(jax.random.normal(ks[1], (B, T, G, dk)))
    v = jax.random.normal(ks[2], (B, T, H, dv))
    g = -jax.random.uniform(ks[3], (B, T, H)) * 3
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    # documents that start off every chunk grid tried, then padding
    seg = jnp.asarray([[1] * 30 + [2] * 50 + [0] * 16, [1] * 96])
    return (q, k, v, g, beta), seg


def recurrence(q, k, v, g, beta, seg):
    """The rule a token at a time over packed rows: the state is zeroed
    where a token's document differs from the one before it."""
    r = v.shape[2] // q.shape[2]
    q, k = jnp.repeat(q, r, 2), jnp.repeat(k, r, 2)

    def step(carry, xs):
        S, prev = carry
        q_t, k_t, v_t, g_t, b_t, seg_t = xs
        S = jnp.where((seg_t == prev)[:, None, None, None], S, 0)
        S = S * jnp.exp(g_t)[..., None, None]
        d = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t))
        S = S + k_t[..., None] * d[:, :, None, :]
        return (S, seg_t), jnp.einsum("bhkv,bhk->bhv", S, q_t)

    S0 = jnp.zeros(v.shape[:1] + (v.shape[2], q.shape[3], v.shape[3]))
    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta, seg))
    out = jax.lax.scan(step, (S0, -jnp.ones(seg.shape[:1], seg.dtype)), xs)[1]
    return jnp.moveaxis(out, 0, 1)


@pytest.mark.parametrize("chunk", [16, 64, 40])  # 64 and 40 do not divide 96
def test_the_chunked_rule_matches_the_recurrence(chunk):
    args, seg = rule_inputs()
    got = jax.jit(gdn.gated_delta_rule, static_argnums=6)(*args, seg, chunk)
    want = jax.jit(recurrence)(*args, seg)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)
    grads = jax.jit(jax.grad(lambda *a: jnp.sum(
        gdn.gated_delta_rule(*a, seg, chunk) ** 2), argnums=(0, 1, 2, 3, 4)))
    grads_ref = jax.jit(jax.grad(
        lambda *a: jnp.sum(recurrence(*a, seg) ** 2),
        argnums=(0, 1, 2, 3, 4)))
    for a, b in zip(grads(*args), grads_ref(*args)):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-3)


def test_the_rule_takes_the_decays_a_drawn_a_log_gives():
    """``g`` of -20 and less a token: every exponent is that of a
    non-positive difference, so nothing overflows, forward or backward."""
    (q, k, v, g, beta), seg = rule_inputs()
    g = g * 20.0
    out, grads = jax.jit(jax.value_and_grad(lambda *a: jnp.sum(
        gdn.gated_delta_rule(*a, seg, 64) ** 2), argnums=(0, 1, 2, 3, 4)))(
        q, k, v, g, beta)
    assert np.isfinite(out) and all(
        bool(jnp.isfinite(x).all()) for x in grads)


def test_the_inverse_of_a_unit_lower_triangle():
    A = jnp.tril(jax.random.normal(jax.random.PRNGKey(0), (3, 64, 64)), -1) * .2
    M = gdn._unit_lower_inverse(A)
    np.testing.assert_allclose(
        M @ (jnp.eye(64) + A), jnp.broadcast_to(jnp.eye(64), A.shape),
        atol=1e-4)
    w = jax.random.normal(jax.random.PRNGKey(1), A.shape)
    got = jax.grad(lambda a: jnp.sum(gdn._unit_lower_inverse(a) * w))(A)
    want = jax.grad(lambda a: jnp.sum(
        jnp.linalg.inv(jnp.eye(64) + a) * w))(A)
    np.testing.assert_allclose(jnp.tril(got, -1), jnp.tril(want, -1),
                               atol=1e-3, rtol=1e-3)


# ---- (d) packed rows, runs, remat ----

def test_a_packed_row_equals_each_of_its_documents_alone():
    """Documents that start off the chunk grid (16) at 29, 58 and 79, then
    padding: against the reference on each document alone (two lengths,
    so that it is traced twice), and the program on one alone."""
    cfg, params = model()
    lens = (29, 29, 21, 29)
    row, seg, pos, docs = packed_row(lens, 128)
    packed = system_logits(params, cfg, row, seg, pos)[0]
    start = 0
    for doc in docs:
        np.testing.assert_allclose(
            packed[start:start + len(doc)], ref_logits(params, doc), **TOL)
        start += len(doc)
    np.testing.assert_allclose(
        packed[58:79], system_logits(params, cfg, docs[2]), **TOL)


def test_what_the_backward_finds_kept_counts_the_mixers_projections():
    cfg, _ = model()
    kept = transformer.remat_kept_bytes(cfg, 1000, 2)
    assert kept["full"] == 4 * 1000 * 64 * 2
    assert kept["attention"] == kept["full"]  # no kernel on the CPU path
    moe_w = 8 + 1 + 2 * 32
    gdn_w = cfg.gdn.qkvz_dim + cfg.gdn.ba_dim + 64 + moe_w
    full_w = 2 * cfg.q_dim + 2 * cfg.kv_dim + 64 + moe_w
    assert kept["matmuls"] - kept["full"] == 1000 * 2 * (3 * gdn_w + full_w)


# ---- (e) the share (model-configs guide, section 4) ----

def test_the_parts_all_shares_give_add_up_to_the_uncut_layer():
    """The routed parts of the four shares of two experts, and the shared
    expert counted once, add up to the uncut expert layer — in the program
    and in the reference."""
    cfg, params = model()
    lp = {k: w[0] for k, w in params["layers"][GDN].items()}
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 24, 64))
    whole, aux = moe.moe_mlp(x, lp, cfg.moe)
    assert float(aux["dropped_frac"]) == 0.0
    routed_only = {k: w for k, w in lp.items() if not k.startswith("s_")}
    parts = []
    for shard in range(4):
        share = dataclasses.replace(
            cfg.moe, num_experts=2, router_experts=8, first_expert=2 * shard)
        held = {**routed_only, **{k: lp[k][2 * shard:2 * shard + 2]
                                  for k in ("e_gate", "e_up", "e_down")}}
        y, aux = moe.moe_mlp(x, held, share)
        parts.append(y)
        keys = {**HF_KEYS, "num_experts": 2, "num_routed_experts": 8,
                "expert_shard_count": 4, "expert_shard_index": shard}
        np.testing.assert_allclose(y[0], ref.routed(x[0], keys, held), **TOL)
    shared_once = ref.shared(x[0], lp)
    np.testing.assert_allclose(sum(parts)[0] + shared_once, whole[0], **TOL)
    np.testing.assert_allclose(ref.moe(x[0], HF_KEYS, lp), whole[0], **TOL)
    # the gate a token: a zero gate weight halves the shared expert
    halved = moe.moe_mlp(x, {**lp, "s_sig": jnp.zeros((64, 1))}, cfg.moe)[0]
    ungated = moe.moe_mlp(x, {k: w for k, w in lp.items() if k != "s_sig"},
                          dataclasses.replace(
                              cfg.moe, shared_expert_gate=False))[0]
    np.testing.assert_allclose(
        halved - sum(parts), 0.5 * (ungated - sum(parts)), **TOL)


# ---- (f) attention's own pieces ----

def test_partial_rotary_against_a_hand_rolled_rotation():
    cfg, _ = model()
    T, H, dh, rd = 11, 4, 32, 8
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(6), (1, T, H, dh)))
    pos = jnp.arange(T)[None]
    cos, sin = transformer.rope_tables_by_kind(cfg, pos)[FULL]
    assert cos.shape[-1] == rd
    got = np.asarray(transformer.apply_rope(jnp.asarray(x), cos, sin))
    want = x.copy()
    for t in range(T):
        for i in range(rd // 2):
            ang = t * 1e7 ** (-2 * i / rd)
            a, b = x[0, t, :, i], x[0, t, :, i + rd // 2]
            want[0, t, :, i] = a * np.cos(ang) - b * np.sin(ang)
            want[0, t, :, i + rd // 2] = b * np.cos(ang) + a * np.sin(ang)
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_array_equal(got[..., rd:], x[..., rd:])
    np.testing.assert_allclose(
        ref.rope(jnp.asarray(x[0]), 1e7, rd), want[0], atol=1e-5)


def test_a_zero_centred_weight_of_zero_is_a_plain_weight_of_one():
    x = jax.random.normal(jax.random.PRNGKey(7), (5, 64))
    np.testing.assert_allclose(
        transformer.rms_norm_zero_centered(x, jnp.zeros(64), 1e-6),
        transformer.rms_norm(x, jnp.ones(64), 1e-6), atol=1e-6)
    w = 0.1 * jax.random.normal(jax.random.PRNGKey(8), (64,))
    np.testing.assert_allclose(
        transformer.rms_norm_zero_centered(x, w, 1e-6),
        ref.rms(x, w, 1e-6), atol=1e-6)


# ---- (g) checkpoints ----

def test_hf_names_round_trip_with_both_interleaved_layouts():
    cfg, params = model()
    sd = hf.params_to_hf_state_dict(params, cfg)
    back = hf.params_from_hf_state_dict(sd, cfg)
    flat, flat_back = hf.flatten_pytree(params), hf.flatten_pytree(back)
    assert set(flat) == set(flat_back)
    for name in flat:
        np.testing.assert_array_equal(np.asarray(flat[name]),
                                      flat_back[name], err_msg=name)
    G, H, dk, dv, r = 2, 4, 16, 16, 2
    lp = {k: np.asarray(w[1]) for k, w in params["layers"][GDN].items()}
    qkvz = sd["model.layers.1.linear_attn.in_proj_qkvz.weight"]
    ba = sd["model.layers.1.linear_attn.in_proj_ba.weight"]
    assert qkvz.shape == (2 * G * dk + 2 * H * dv, 64)
    assert ba.shape == (2 * H, 64)
    per = 2 * dk + 2 * r * dv  # a key head's rows: q | k | v v | z z
    ours = {"q": 0, "k": G * dk, "v": 2 * G * dk, "z": 2 * G * dk + H * dv}
    for j in range(G):
        for c in range(dk):  # column by column
            np.testing.assert_array_equal(
                qkvz[j * per + c], lp["gdn_qkvz"][:, ours["q"] + j * dk + c])
            np.testing.assert_array_equal(
                qkvz[j * per + dk + c],
                lp["gdn_qkvz"][:, ours["k"] + j * dk + c])
        for c in range(r * dv):
            np.testing.assert_array_equal(
                qkvz[j * per + 2 * dk + c],
                lp["gdn_qkvz"][:, ours["v"] + j * r * dv + c])
            np.testing.assert_array_equal(
                qkvz[j * per + 2 * dk + r * dv + c],
                lp["gdn_qkvz"][:, ours["z"] + j * r * dv + c])
        for c in range(r):
            np.testing.assert_array_equal(
                ba[j * 2 * r + c], lp["gdn_ba"][:, j * r + c])
            np.testing.assert_array_equal(
                ba[j * 2 * r + r + c], lp["gdn_ba"][:, H + j * r + c])
    assert sd["model.layers.1.linear_attn.conv1d.weight"].shape == (
        2 * G * dk + H * dv, 1, 4)
    full = {k: np.asarray(w[0]) for k, w in params["layers"][FULL].items()}
    q_proj = sd["model.layers.3.self_attn.q_proj.weight"]
    assert q_proj.shape == (2 * 4 * 32, 64)
    for h in range(4):  # rows by head: [q_h | gate_h]
        for c in range(32):
            np.testing.assert_array_equal(
                q_proj[h * 64 + c], full["wq"][:, h * 32 + c])
            np.testing.assert_array_equal(
                q_proj[h * 64 + 32 + c], full["wg"][:, h * 32 + c])
    assert sd["model.layers.0.mlp.shared_expert_gate.weight"].shape == (1, 64)
    assert sd["model.layers.0.mlp.gate.weight"].shape == (8, 64)


# ---- (h) where the block cannot go yet ----

@pytest.mark.parametrize("where", ["ring", "pipeline", "generate"])
def test_where_the_block_cannot_go_yet_is_refused_by_name(where):
    cfg, params = model()
    if where == "ring":
        from areal_tpu.parallel import ring

        assert ring.ring_refusal(cfg) == "gated_delta_rule"
        assert "gated_delta_rule" in ring.RING_REFUSALS
    elif where == "pipeline":
        from jax.sharding import Mesh

        from areal_tpu.parallel import pipeline

        mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("pp",))
        pipeline._WARNED_FALLBACKS.discard("gated_delta_rule")
        assert pipeline.pick_pp_microbatches(mesh, cfg, 4) is None
        assert "gated_delta_rule" in pipeline._WARNED_FALLBACKS
    else:
        from areal_tpu.models import generate

        assert generate.decode_refusal(cfg).startswith(
            "delta_rule_decode_state")
        with pytest.raises(NotImplementedError,
                           match="delta_rule_decode_state"):
            transformer.init_kv_cache(cfg, 1, 8)
        with pytest.raises(NotImplementedError,
                           match="delta_rule_decode_state"):
            transformer.forward(params, cfg, tokens()[None],
                                jnp.arange(43)[None],
                                segment_ids=jnp.ones((1, 43), jnp.int32))


def test_the_specs_mirror_the_parameters():
    from jax.sharding import PartitionSpec as P

    from areal_tpu.parallel.sharding import param_partition_specs

    cfg, params = model()
    specs = param_partition_specs(cfg)
    assert jax.tree.structure(
        jax.tree.map(lambda s: 0, specs, is_leaf=lambda x: isinstance(x, P))
    ) == jax.tree.structure(jax.tree.map(lambda a: 0, params))
    for s, a in zip(jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)),
                    jax.tree.leaves(params)):
        assert len(s) == a.ndim


# ---- (i) what the benchmark reads ----

def test_the_scopes_and_counts_the_benchmark_reads_are_the_programs():
    from areal_tpu.base import telemetry
    from benchmark import gdn_trace

    assert set(gdn_trace.SCOPES) | {"shared_expert_gate"} == set(
        telemetry.GDN_SCOPES)
    cfg, params = model(chunk=64)
    before = dict(gdn.geometry_counts())
    text = system_logits.lower(params, cfg, tokens()).as_text(
        debug_info=True)
    for scope in telemetry.GDN_SCOPES + (
            "shared_expert", "moe_router", "moe_experts", "attention",
            "attn_gate"):
        assert scope in text, scope
    # one rule a run of Gated DeltaNet blocks a program, at its geometry
    key = (1, 43, 64, 2, 4, 16, 16)
    assert gdn.geometry_counts()[key] - before.get(key, 0) == 1


def test_the_live_flop_count_counts_the_rule_in_place_of_attention():
    from areal_tpu.base import monitor

    cfg, _ = model()
    flops = monitor.model_flops_per_token(cfg, 1000.0)
    as_attention = monitor.model_flops_per_token(
        dataclasses.replace(cfg, gdn=None), 1000.0)
    assert 0 < flops != as_attention
    # a linear mixer's count does not grow with the document
    grow = (monitor.model_flops_per_token(cfg, 2000.0) - flops)
    assert grow == pytest.approx(3.0 * 2 * 2 * cfg.q_dim * 1000.0)


def test_document_starts_inside_a_chunk_are_a_gauge_of_the_train_step():
    """``train/gdn_resets_in_chunk_per_row``: document starts off the
    chunk grid over the rows that hold any, of one train batch's grids."""
    from areal_tpu.api.data import MicroBatchSpec, SequenceSample
    from areal_tpu.api.train_config import TelemetryConfig
    from areal_tpu.backend.jax_train import JaxTrainEngine
    from areal_tpu.base import telemetry

    assert gdn.resets_in_chunk([0, 64, 70, 128, 200], 256, 64) == 2
    cfg, params = model()
    eng = JaxTrainEngine(cfg, params, compute_dtype="float32",
                         length_bucket=16, rows_bucket=1, seqs_bucket=4)
    lens = [9, 12, 7, 14, 10, 11, 13, 8]
    rng = np.random.RandomState(0)
    sample = SequenceSample.from_default(
        ids=[f"s{i}" for i in range(len(lens))],
        data={"packed_input_ids": rng.randint(
            2, 97, sum(lens)).astype(np.int32)},
        seqlens=lens)
    telemetry.configure("t", "t", "trainer", 0,
                        TelemetryConfig(enabled=True), push=False)
    try:
        ub = eng.upload_uniform(sample, MicroBatchSpec(max_tokens_per_mb=48))
        got = telemetry.get().snapshot()["gauges"][
            "train/gdn_resets_in_chunk_per_row"]
    finally:
        telemetry.shutdown()
    rows = sum(len({r for r, _ in mb.layout.placements}) for mb in ub.mbs)
    inside = sum(0 < col and col % 16 != 0 for mb in ub.mbs
                 for _, col in mb.layout.placements)
    assert got == pytest.approx(inside / rows) and inside > 0


def test_where_the_mixers_norms_ran_is_a_gauge_of_the_train_step():
    """``train/gdn_kernel_frac`` and ``train/gdn_norms_in_kernel_frac``: of
    the rules and the mixers traced, the share on the Pallas kernel pair
    and the share whose two norms ran inside it — gauges and attributes of
    the ``train/fwd_bwd`` span (here the tiny model is off the kernels'
    lane grid: its own mixers count ``xla``)."""
    from areal_tpu.api.data import MicroBatchSpec, SequenceSample
    from areal_tpu.api.model import FinetuneSpec
    from areal_tpu.api.train_config import OptimizerConfig, TelemetryConfig
    from areal_tpu.backend.jax_train import JaxTrainEngine
    from areal_tpu.base import telemetry

    cfg, params = model()
    eng = JaxTrainEngine(cfg, params, OptimizerConfig(type="sgd", lr=1e-2),
                         FinetuneSpec(1, 8, 4), compute_dtype="float32",
                         length_bucket=16, rows_bucket=1, seqs_bucket=4)
    lens = [9, 12, 7, 14]
    rng = np.random.RandomState(0)
    sample = SequenceSample.from_default(
        ids=[f"s{i}" for i in range(len(lens))],
        data={"packed_input_ids": rng.randint(
            2, 97, sum(lens)).astype(np.int32),
            "loss_mask": np.ones(sum(lens), np.float32)},
        seqlens=lens)

    def sq_loss(logits, batch):
        w = (batch["segment_ids"] > 0).astype(jnp.float32)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return jnp.sum(jnp.sum(lp * lp, axis=-1) * w), {"n": jnp.sum(w)}

    before = gdn.mixer_norm_counts().get("xla", 0)
    telemetry.configure("t", "t", "trainer", 0,
                        TelemetryConfig(enabled=True), push=False)
    try:
        for _ in range(2):  # the first step traces; the second reads
            eng.train_batch(sample, MicroBatchSpec(max_tokens_per_mb=48),
                            sq_loss, lambda mb: mb.n_tokens)
        snap = telemetry.get().snapshot()
    finally:
        telemetry.shutdown()
    assert gdn.mixer_norm_counts()["xla"] > before
    gauges = snap["gauges"]
    assert gauges["train/gdn_kernel_frac"] == gdn.rule_kernel_frac()
    assert gauges["train/gdn_norms_in_kernel_frac"] == pytest.approx(
        gdn.norms_in_kernel_frac())
    assert 0.0 <= gauges["train/gdn_norms_in_kernel_frac"] < 1.0
    spans = [s for s in snap["spans"] if s["name"] == "train/fwd_bwd"]
    assert spans[-1]["attrs"]["gdn_norms_in_kernel_frac"] == gauges[
        "train/gdn_norms_in_kernel_frac"]
