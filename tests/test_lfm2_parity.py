"""LFM2-MoE (``model_type`` lfm2_moe) through the system against the
benchmark's plain reference (``benchmark/reference_lfm2.py``: float32, the
short convolution as three shifted products of one document, attention as
a masked softmax, every held expert on every token, one document at a
time) on seeded weights, on the CPU at a tiny size: hidden 32, 4 query / 2
key-value heads of 8, a dense FFN of 48 on the leading block, 8 experts of
24 (3 a token) after it, the cut's pattern ``c(dense) A c c`` — all three
block kinds.

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

from areal_tpu.models import hf, moe, shortconv, transformer
from areal_tpu.models.config import CONV, FULL
from benchmark import reference_lfm2 as ref

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HF_KEYS = {
    "model_type": "lfm2_moe", "num_hidden_layers": 4, "num_dense_layers": 1,
    "layer_types": ["conv", "full_attention", "conv", "conv"],
    "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 48, "moe_intermediate_size": 24, "vocab_size": 67,
    "norm_eps": 1e-5, "conv_L_cache": 3, "conv_bias": False,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "num_experts": 8, "num_experts_per_tok": 3, "use_expert_bias": True,
    "norm_topk_prob": True, "routed_scaling_factor": 1,
    "max_position_embeddings": 4096,
}
TOL = dict(atol=3e-4, rtol=3e-4)
NORMS = ("ln1", "ln2", "final_ln", "q_norm", "k_norm")
T = 29


@functools.lru_cache(maxsize=None)
def model(seed=0, scale=0.3):
    """(config, float32 params): init_params with the matrices scaled up
    (so that every mixer and expert matters), the norm weights random
    around 1 and the choice bias drawn wide enough to change choices."""
    cfg = hf.config_from_hf(types.SimpleNamespace(**HF_KEYS))

    @jax.jit
    def build():
        flat = hf.flatten_pytree(
            transformer.init_params(cfg, jax.random.PRNGKey(seed)))
        rngs = jax.random.split(jax.random.PRNGKey(seed + 1), len(flat))
        for (name, x), k in zip(sorted(flat.items()), rngs):
            leaf = name.split("/")[-1]
            if leaf in NORMS:
                flat[name] = 1.0 + 0.1 * jax.random.normal(k, x.shape)
            elif leaf == "router_bias":
                flat[name] = 0.1 * jax.random.normal(k, x.shape)
            elif leaf == "embedding":
                flat[name] = x * 40.0
            elif leaf != "sc_conv":
                flat[name] = x * (scale / 0.02)
        return hf.unflatten_pytree(flat)

    return cfg, build()


def tokens(seed=0, n=T):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(2, HF_KEYS["vocab_size"], n), jnp.int32)


def packed_row(lens, width, seed=10):
    """(row [1, width], segment ids, positions, the documents) of documents
    of ``lens`` tokens packed one behind another, then padding."""
    docs = [tokens(seed + i, n) for i, n in enumerate(lens)]
    pad = width - sum(lens)
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
    B, n = tok.shape
    seg = jnp.ones((B, n), jnp.int32) if seg is None else seg
    pos = jnp.broadcast_to(jnp.arange(n), (B, n)) if pos is None else pos
    out, _ = transformer.forward(
        params, cfg, tok, pos, segment_ids=seg, attn_impl="reference",
        return_kv=False, remat=remat)
    return out[0] if one else out


@functools.lru_cache(maxsize=None)
def logits_of_the_system():
    cfg, params = model()
    return np.asarray(system_logits(params, cfg, tokens()))


def logprobs_of(lg, tok):
    lp = jax.nn.log_softmax(lg[:-1], -1)
    return jnp.take_along_axis(lp, tok[1:, None], -1)[:, 0]


# ---- (a) the family ----

def test_the_family_reads_the_blocks():
    cfg, params = model()
    assert cfg.layer_kinds == ("conv_dense", FULL, CONV, CONV)
    assert cfg.block_counts() == {"conv/dense": 1, "full/experts": 1,
                                  "conv/experts": 2}
    assert cfg.is_hybrid and cfg.has_cacheless_layers
    assert cfg.head_dim == 8 and cfg.use_qk_norm and cfg.tie_word_embeddings
    assert cfg.rotary_base == 1e6 and cfg.rms_norm_eps == 1e-5
    assert cfg.shortconv.kernel == 3 and cfg.n_expert_layers == 3
    assert cfg.moe.router_score == "sigmoid" and cfg.moe.aux_loss_coeff == 0
    assert cfg.moe.shared_intermediate_dim is None
    assert set(params["layers"]) == {"conv_dense", "full", "conv"}
    assert "lm_head" not in params
    assert params["layers"]["conv"]["sc_in"].shape == (2, 32, 96)
    assert params["layers"]["conv"]["sc_conv"].shape == (2, 3, 32)
    assert "w_gate" in params["layers"]["conv_dense"]
    assert "router" not in params["layers"]["conv_dense"]
    assert "q_norm" in params["layers"]["full"]
    assert "q_norm" not in params["layers"]["conv"]
    # the run scan: one run a kind for the cut's pattern
    assert [n for _, n in transformer.period_runs(
        ("conv_dense", FULL, CONV, CONV, CONV))] == [1, 1, 3]


def test_the_config_goes_out_and_comes_back():
    cfg, _ = model()
    d = hf.hf_config_dict(cfg)
    assert d["model_type"] == "lfm2_moe" and d["layer_types"] == HF_KEYS[
        "layer_types"]
    assert d["conv_L_cache"] == 3 and d["num_dense_layers"] == 1
    assert d["norm_eps"] == 1e-5 and d["use_expert_bias"] is True
    assert d["rope_parameters"]["rope_theta"] == 1e6
    assert hf.config_from_hf(types.SimpleNamespace(**d)) == cfg
    share = {**HF_KEYS, "num_experts": 2, "num_routed_experts": 8,
             "expert_shard_count": 4, "expert_shard_index": 3}
    scfg = hf.config_from_hf(types.SimpleNamespace(**share))
    assert (scfg.moe.n_routed, scfg.moe.first_expert) == (8, 6)
    back = hf.hf_config_dict(scfg)
    assert (back["num_routed_experts"], back["expert_shard_index"]) == (8, 3)
    # what a fresh choice bias is drawn at: written only where it is not
    # the matrices' 0.02, and what init_params then draws it at
    assert "expert_bias_init_std" not in d
    small = hf.config_from_hf(types.SimpleNamespace(
        **HF_KEYS, expert_bias_init_std=0.005))
    assert small.moe.router_bias_init_std == 0.005
    assert hf.hf_config_dict(small)["expert_bias_init_std"] == 0.005
    drawn = moe.init_moe_params(small, jax.random.PRNGKey(2), jnp.float32, 64)
    assert 0.8 < float(jnp.std(drawn["router_bias"])) / 0.005 < 1.2
    assert 0.9 < float(jnp.std(drawn["router"])) / 0.02 < 1.1


@pytest.mark.parametrize("key,value,name", [
    ("conv_bias", True, "conv_bias"),
    ("use_expert_bias", False, "use_expert_bias"),
    ("layer_types", ["conv", "sliding_attention", "conv", "conv"],
     "layer_types"),
    ("rope_parameters", {"rope_type": "yarn", "rope_theta": 1e6,
                         "factor": 4.0,
                         "original_max_position_embeddings": 4096},
     "rope_scaling"),
])
def test_keys_of_the_family_that_are_not_built_are_refused_by_name(
        key, value, name):
    with pytest.raises(NotImplementedError, match=name):
        hf.config_from_hf(types.SimpleNamespace(**{**HF_KEYS, key: value}))


def test_parameter_count_at_the_published_widths():
    """``param_count`` of the benchmark's cut equals the sum of its
    leaves' sizes (shapes only: nothing is allocated) and the number in
    the configuration file; the mixers' and experts' sizes are ISSUE 56's
    reckoning."""
    from benchmark import shortconv_cost, weights

    with open(os.path.join(REPO, "benchmark", "configs",
                           "lfm2-24b-a2b.json")) as f:
        keys = json.load(f)
    cfg = weights.model_config(keys)
    shapes = jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert n == transformer.param_count(cfg) == keys["n_parameters"]
    assert n == 469_285_248
    assert shortconv.shortconv_param_count(cfg.shortconv, 2048) == 16_783_360
    assert cfg.moe.n_routed == 64 and cfg.moe.top_k == 4
    assert shapes["layers"]["conv"]["router"].shape == (3, 2048, 64)
    assert shapes["layers"]["conv"]["e_gate"].shape == (3, 8, 2048, 1536)
    # what a token multiplies through on the share: the benchmark's N
    assert shortconv_cost.share_params(keys) == 186_122_240
    assert 0 < transformer.activated_param_count(cfg) < n


# ---- (b) the whole model against the reference ----

def test_logprobs_match_the_reference():
    cfg, params = model()
    tok = tokens()
    want = jax.jit(ref.token_logprobs, static_argnums=1)(
        params, _frozen(HF_KEYS), tok)
    got = logprobs_of(jnp.asarray(logits_of_the_system()), tok)
    np.testing.assert_allclose(got, want, **TOL)


class _frozen(dict):
    """The HF keys as a static (hashable) argument of ``jax.jit``."""

    def __hash__(self):
        return hash(json.dumps(self, sort_keys=True))


@functools.lru_cache(maxsize=None)
def reference_loss_and_gradients():
    _, params = model()
    return jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, HF_KEYS, tokens())))(params)


@pytest.mark.parametrize("remat", ["full", "matmuls"])
def test_loss_and_every_gradient_match_the_reference(remat):
    cfg, params = model()
    tok = tokens()
    loss, grads = jax.jit(jax.value_and_grad(lambda p: -jnp.mean(logprobs_of(
        system_logits(p, cfg, tok, remat=remat), tok))))(params)
    want_loss, want = reference_loss_and_gradients()
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    got_g, want_g = hf.flatten_pytree(grads), hf.flatten_pytree(want)
    assert set(got_g) == set(want_g)
    for name in sorted(want_g):
        scale = float(jnp.abs(want_g[name]).max()) or 1.0
        np.testing.assert_allclose(
            got_g[name] / scale, want_g[name] / scale, atol=2e-4,
            err_msg=name)
    # the choice bias is a buffer: no gradient reaches it, on either side
    for kind in ("full", "conv"):
        assert not np.any(got_g[f"layers/{kind}/router_bias"])
        assert not np.any(want_g[f"layers/{kind}/router_bias"])


def test_the_ppo_loss_of_the_reference_has_the_surrogates_gradient():
    """Inside the clip range the PPO surrogate's gradient is the
    advantage-weighted logprob gradient."""
    _, params = model()
    keys = {**HF_KEYS, "num_hidden_layers": 2}  # c(dense) A: both FFNs
    tok = tokens(n=13)
    adv = jax.random.normal(jax.random.PRNGKey(3), (12,))
    mask = (jnp.arange(12) >= 5).astype(jnp.float32)
    w = adv * mask / mask.sum()

    @jax.jit
    def both(p):
        old = jax.lax.stop_gradient(ref.token_logprobs(p, keys, tok))
        return (jax.grad(ref.ppo_loss)(p, keys, tok, old, adv, mask),
                jax.grad(ref.loss)(p, keys, tok, w))

    g, want = both(params)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5 * (
            1.0 + float(jnp.abs(b).max())))


@pytest.mark.parametrize("which", ref.WRONG)
def test_a_wrong_reference_is_told_apart(which):
    """Every fault the benchmark's limits are set against moves the
    logprobs far outside what separates the system from the reference."""
    _, params = model()
    tok = tokens()
    got = logprobs_of(jnp.asarray(logits_of_the_system()), tok)
    wrong = ref.token_logprobs(params, HF_KEYS, tok, frozenset({which}))
    # the two roundings move little in float32 at this size, but move
    floor = 2e-5 if which == "conv_products_in_bfloat16" else 3e-3
    assert float(jnp.abs(got - wrong).max()) > floor, which


# ---- (c) the mixer alone ----

def hand_rolled(u, w_in, w, w_out, seg):
    """The mixer a token and a tap at a time: [T, D] -> [T, D]."""
    n, d = u.shape
    bcx = u @ w_in
    Bg, Cg, x = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    z = Bg * x
    K = w.shape[0]
    rows = []
    for t in range(n):
        c = jnp.zeros(d)
        for j in range(K):  # tap j reads the token K - 1 - j back
            s = t - (K - 1) + j
            if s >= 0 and seg[s] == seg[t] and seg[t] > 0:
                c = c + w[j] * z[s]
        rows.append(Cg[t] * c)
    return jnp.stack(rows) @ w_out


def test_the_mixer_against_a_three_tap_loop_with_starts_at_every_offset():
    """Forward and every gradient, on a row whose documents start at every
    offset of a tap: documents of 1, 2, 3, 1, 1, 4 tokens, then padding."""
    lens = [1, 2, 3, 1, 1, 4]
    seg = sum(([i + 1] * n for i, n in enumerate(lens)), []) + [0, 0]
    n, d = len(seg), 8
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    u = jax.random.normal(ks[0], (n, d))
    lp = {"sc_in": 0.5 * jax.random.normal(ks[1], (d, 3 * d)),
          "sc_conv": jax.random.uniform(ks[2], (3, d), minval=-0.6,
                                        maxval=0.6),
          "sc_out": 0.5 * jax.random.normal(ks[3], (d, d))}
    weight = jax.random.normal(ks[4], (n, d)) * (
        jnp.asarray(seg)[:, None] > 0)

    def sys_loss(u, lp):
        y = shortconv.shortconv_mixer(u[None], lp, jnp.asarray([seg]))[0]
        return jnp.sum(weight * y), y

    def hand_loss(u, lp):
        y = hand_rolled(u, lp["sc_in"], lp["sc_conv"], lp["sc_out"], seg)
        return jnp.sum(weight * y), y

    (g, got), (w, want) = (
        jax.jit(jax.grad(f, argnums=(0, 1), has_aux=True))(u, lp)
        for f in (sys_loss, hand_loss))
    real = np.asarray(seg) > 0
    np.testing.assert_allclose(got[real], want[real], atol=1e-5)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(w)):
        np.testing.assert_allclose(a, b, atol=1e-5)
    # the reference's three shifted products, a document at a time
    keys = {**HF_KEYS, "hidden_size": d}
    start = 0
    for m in lens:
        np.testing.assert_allclose(
            ref.shortconv(u[start:start + m], keys, lp),
            want[start:start + m], atol=1e-5)
        start += m
    # the taps' order: w[K - 1] multiplies the token itself
    one = shortconv.gated_conv(
        jnp.ones((1, 4, 3)), jnp.asarray([[0.], [0.], [5.]]),
        jnp.ones((1, 4), jnp.int32))
    np.testing.assert_array_equal(one[0, :, 0], [5., 5., 5., 5.])


def test_the_products_stay_in_float32_until_the_second_gate():
    """In bfloat16 the mixer's pass rounds once, at ``y``: it equals the
    float32 pass on the same (rounded) inputs, rounded."""
    ks = jax.random.split(jax.random.PRNGKey(9), 2)
    bcx = jax.random.normal(ks[0], (1, 12, 24)).astype(jnp.bfloat16)
    w = jax.random.uniform(ks[1], (3, 8), minval=-0.6, maxval=0.6
                           ).astype(jnp.bfloat16)
    seg = jnp.asarray([[1] * 5 + [2] * 7])
    got = shortconv.gated_conv(bcx, w, seg)
    assert got.dtype == jnp.bfloat16
    want = shortconv.gated_conv(bcx.astype(jnp.float32),
                                w.astype(jnp.float32), seg)
    np.testing.assert_array_equal(got, want.astype(jnp.bfloat16))


def test_a_packed_row_of_many_short_documents_equals_each_alone():
    cfg, params = model()
    lens = [5, 1, 2, 9, 1, 5, 2, 9, 2]  # some of 1 and 2 tokens
    row, seg, pos, docs = packed_row(lens, 40)
    got = system_logits(params, cfg, row, seg, pos)[0]
    start = 0
    for doc in docs:
        alone = jax.jit(ref.logits, static_argnums=1)(
            params, _frozen(HF_KEYS), doc)
        np.testing.assert_allclose(got[start:start + len(doc)], alone, **TOL)
        start += len(doc)


def test_what_the_backward_finds_kept_counts_the_mixers_projections():
    cfg, _ = model()
    kept = transformer.remat_kept_bytes(cfg, 1000, 2)
    assert kept["full"] == 4 * 1000 * 32 * 2
    assert kept["attention"] == kept["full"]  # no kernel on the CPU path
    conv_w, full_w = 4 * 32, cfg.q_dim + 2 * cfg.kv_dim + 32
    assert kept["matmuls"] - kept["full"] == 1000 * 2 * (
        (conv_w + 2 * 48) + (full_w + 8) + 2 * (conv_w + 8))


# ---- (d) the share and the choice bias ----

def test_the_parts_all_eight_shares_give_add_up_to_the_uncut_layer():
    """The parts of the eight shares of one expert each add up to the
    uncut expert layer — in the program and in the reference (there is no
    shared expert to count once)."""
    cfg, params = model()
    lp = {k: w[0] for k, w in params["layers"][CONV].items()}
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 24, 32))
    whole, aux = moe.moe_mlp(x, lp, cfg.moe)
    assert float(aux["dropped_frac"]) == 0.0
    parts = []
    for shard in range(8):
        share = dataclasses.replace(
            cfg.moe, num_experts=1, router_experts=8, first_expert=shard)
        held = {**lp, **{k: lp[k][shard:shard + 1]
                         for k in ("e_gate", "e_up", "e_down")}}
        y, _ = moe.moe_mlp(x, held, share)
        parts.append(y)
        keys = {**HF_KEYS, "num_experts": 1, "num_routed_experts": 8,
                "expert_shard_count": 8, "expert_shard_index": shard}
        np.testing.assert_allclose(y[0], ref.moe(x[0], keys, held), **TOL)
    np.testing.assert_allclose(sum(parts)[0], whole[0], **TOL)
    np.testing.assert_allclose(ref.moe(x[0], HF_KEYS, lp), whole[0], **TOL)


def test_the_bias_chooses_and_nothing_else():
    """The choice is by score + bias, the gates are the chosen SCORES
    renormalised: a bias that lifts one expert into every token's choice
    changes the choice and leaves the other gates' ratios alone."""
    cfg, params = model()
    lp = {k: w[0] for k, w in params["layers"][CONV].items()}
    x = jax.random.normal(jax.random.PRNGKey(6), (17, 32))
    scores, idx = ref.chosen(x, HF_KEYS, lp)
    _, no_bias = ref.chosen(x, HF_KEYS, lp, frozenset(
        {"bias_left_out_of_choice"}))
    assert np.any(np.sort(idx, -1) != np.sort(no_bias, -1))
    lifted = {**lp, "router_bias": jnp.zeros(8).at[5].set(10.0)}
    g = ref.gates(x, HF_KEYS, lifted)
    assert np.all(g[:, 5] > 0)  # chosen by every token ...
    np.testing.assert_allclose(  # ... at its score's share, not score + 10
        g[:, 5], scores[:, 5] / (jnp.sum(jnp.where(g > 0, scores, 0), -1)
                                 + ref.GATE_EPS), rtol=1e-5)
    np.testing.assert_allclose(jnp.sum(g, -1), 1.0, atol=1e-5)
    got, _ = moe.moe_mlp(x[None], lifted, cfg.moe)
    np.testing.assert_allclose(got[0], ref.moe(x, HF_KEYS, lifted), **TOL)


def test_the_bias_takes_no_optimizer_update():
    """A step whose gradients are zero moves every decayed weight and
    leaves the choice bias bit for bit (``moe.BUFFER_LEAVES``)."""
    from areal_tpu.api.train_config import OptimizerConfig
    from areal_tpu.backend import jax_train

    _, params = model()
    layers = {"layers": {CONV: {k: params["layers"][CONV][k]
                                for k in ("router", "router_bias")}}}
    tx, _ = jax_train.build_optimizer(
        OptimizerConfig(lr=1e-2, weight_decay=0.1), total_steps=10)
    updates, _ = tx.update(jax.tree.map(jnp.zeros_like, layers),
                           tx.init(layers), layers)
    assert not np.any(np.asarray(updates["layers"][CONV]["router_bias"]))
    assert np.all(np.asarray(updates["layers"][CONV]["router"]) != 0)


# ---- (e) the state dict ----

def test_hf_names_round_trip_with_the_convolutions_layout():
    cfg, params = model()
    sd = hf.params_to_hf_state_dict(params, cfg)
    assert "lm_head.weight" not in sd  # tied
    for name, shape in {
        "model.embed_tokens.weight": (67, 32),
        "model.embedding_norm.weight": (32,),
        "model.layers.0.operator_norm.weight": (32,),
        "model.layers.0.ffn_norm.weight": (32,),
        "model.layers.0.conv.in_proj.weight": (96, 32),
        "model.layers.0.conv.conv.weight": (32, 1, 3),
        "model.layers.0.conv.out_proj.weight": (32, 32),
        "model.layers.0.feed_forward.w1.weight": (48, 32),
        "model.layers.0.feed_forward.w2.weight": (32, 48),
        "model.layers.1.self_attn.q_proj.weight": (32, 32),
        "model.layers.1.self_attn.k_proj.weight": (16, 32),
        "model.layers.1.self_attn.out_proj.weight": (32, 32),
        "model.layers.1.self_attn.q_layernorm.weight": (8,),
        "model.layers.1.feed_forward.gate.weight": (8, 32),
        "model.layers.1.feed_forward.expert_bias": (8,),
        "model.layers.3.feed_forward.experts.7.w3.weight": (24, 32),
        "model.layers.3.feed_forward.experts.7.w2.weight": (32, 24),
    }.items():
        assert sd[name].shape == shape, name
    assert "model.layers.0.feed_forward.gate.weight" not in sd
    assert "model.layers.1.conv.conv.weight" not in sd
    # [C, 1, K] tap by tap: HF's tap k is this repo's row k
    conv = np.asarray(params["layers"][CONV]["sc_conv"])  # layers 2, 3
    for k in range(3):
        np.testing.assert_array_equal(
            sd["model.layers.3.conv.conv.weight"][:, 0, k], conv[1, k])
    # in_proj's rows are [B | C | x]
    np.testing.assert_array_equal(
        sd["model.layers.2.conv.in_proj.weight"][32:64],
        np.asarray(params["layers"][CONV]["sc_in"])[0][:, 32:64].T)
    back = hf.params_from_hf_state_dict(sd, cfg)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(params)):
        np.testing.assert_array_equal(a, b)


# ---- (f) where the block cannot go yet ----

@pytest.mark.parametrize("where", ["ring", "pipeline", "generate"])
def test_where_the_block_cannot_go_yet_is_refused_by_name(where):
    cfg, params = model()
    if where == "ring":
        from areal_tpu.parallel import ring

        assert ring.ring_refusal(cfg) == "short_convolution"
        assert "short_convolution" in ring.RING_REFUSALS
    elif where == "pipeline":
        from jax.sharding import Mesh

        from areal_tpu.parallel import pipeline

        mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("pp",))
        pipeline._WARNED_FALLBACKS.discard("short_convolution")
        assert pipeline.pick_pp_microbatches(mesh, cfg, 4) is None
        assert "short_convolution" in pipeline._WARNED_FALLBACKS
    else:
        from areal_tpu.models import generate

        assert generate.decode_refusal(cfg).startswith(
            "short_conv_decode_state")
        with pytest.raises(NotImplementedError,
                           match="short_conv_decode_state"):
            transformer.init_kv_cache(cfg, 1, 8)
        with pytest.raises(NotImplementedError,
                           match="short_conv_decode_state"):
            transformer.forward(params, cfg, tokens()[None],
                                jnp.arange(T)[None],
                                segment_ids=jnp.ones((1, T), jnp.int32))


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
    conv = specs["layers"][CONV]
    assert conv["sc_conv"] == P(None, None, None)  # channels and taps whole
    assert conv["sc_in"][2] is None and conv["sc_out"][1] is None


# ---- (g) what the benchmark and the operator read ----

def test_the_scopes_and_counts_the_benchmark_reads_are_the_programs():
    from areal_tpu.base import telemetry
    from benchmark import shortconv_trace

    assert shortconv_trace.CONV_SCOPES == telemetry.SHORTCONV_SCOPES
    cfg, params = model()
    before = dict(shortconv.geometry_counts())
    text = system_logits.lower(params, cfg, tokens(1, 31)).as_text(
        debug_info=True)
    for scope in telemetry.SHORTCONV_SCOPES + (
            "moe_router", "moe_experts", "attention", "mlp"):
        assert scope in text, scope
    # one convolution a run of short-convolution blocks a program (the
    # dense block's run, the expert blocks' run), at its geometry
    key = (1, 31, 32, 3)
    assert shortconv.geometry_counts()[key] - before.get(key, 0) == 2


def test_the_live_flop_count_counts_the_mixer_and_the_dense_block():
    from areal_tpu.base import monitor

    cfg, _ = model()
    d, f, fe = 32, 48, 24
    conv = 2 * d * 3 * d + 2 * d * d + 8 * d
    attn = 2 * d * (cfg.q_dim + 2 * cfg.kv_dim) + 2 * cfg.q_dim * d + (
        2 * 2 * cfg.q_dim * 100.0)
    experts = 3 * 3 * 2 * d * fe + 2 * d * 8
    want = (3 * conv + attn + 3 * 2 * d * f + 3 * experts + 2 * d * 67)
    assert monitor.model_flops_per_token(cfg, 100.0, backward=False) == (
        pytest.approx(want))
    # three blocks of four do not grow with the document
    grow = (monitor.model_flops_per_token(cfg, 200.0, backward=False)
            - want)
    assert grow == pytest.approx(2 * 2 * cfg.q_dim * 100.0)


def test_cut_taps_are_a_gauge_of_the_train_step():
    """``train/shortconv_resets_per_row``: document starts behind another
    document over the rows that hold any, of one train batch's grids."""
    from areal_tpu.api.data import MicroBatchSpec, SequenceSample
    from areal_tpu.api.train_config import TelemetryConfig
    from areal_tpu.backend.jax_train import JaxTrainEngine
    from areal_tpu.base import telemetry
    from areal_tpu.system import sentinel

    assert "train/shortconv_resets_per_row" in sentinel.METRIC_CATALOG
    cfg, params = model()
    eng = JaxTrainEngine(cfg, params, compute_dtype="float32",
                         length_bucket=16, rows_bucket=1, seqs_bucket=4)
    lens = [9, 12, 7, 14, 10, 11, 13, 8]
    rng = np.random.RandomState(0)
    sample = SequenceSample.from_default(
        ids=[f"s{i}" for i in range(len(lens))],
        data={"packed_input_ids": rng.randint(
            2, 67, sum(lens)).astype(np.int32)},
        seqlens=lens)
    telemetry.configure("t", "t", "trainer", 0,
                        TelemetryConfig(enabled=True), push=False)
    try:
        ub = eng.upload_uniform(sample, MicroBatchSpec(max_tokens_per_mb=48))
        gauges = telemetry.get().snapshot()["gauges"]
    finally:
        telemetry.shutdown()
    rows = sum(len({r for r, _ in mb.layout.placements}) for mb in ub.mbs)
    docs = sum(len(mb.layout.placements) for mb in ub.mbs)
    assert docs > rows
    assert gauges["train/shortconv_resets_per_row"] == pytest.approx(
        (docs - rows) / rows)
    assert gauges["train/docs_per_row"] == pytest.approx(docs / rows)
