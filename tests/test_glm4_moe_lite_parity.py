"""GLM-4.7-Flash (``model_type`` glm4_moe_lite) through the system against
the benchmark's plain reference (``benchmark/reference_glm4_moe_lite.py``:
float32, latent attention as a masked softmax of one document with the
rotary key rotated once and repeated, every held expert on every token,
one document at a time) on seeded weights, on the CPU at a tiny size:
hidden 32, 4 heads of 12 + 4 / 16 through latents of 12 and 8, a dense FFN
of 48 on the leading block, 8 experts of 24 (3 a token, gates x 1.8)
beside a shared expert after it, the cut's pattern ``D E E``.

Both sides compute in float32 here, so they differ by the order of
float32 sums only; every fault ``reference.WRONG`` names moves logprobs by
1e-3 and more.
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

from areal_tpu.models import hf, mla, moe, transformer
from areal_tpu.models.config import FULL, MLAConfig
from benchmark import reference_glm4_moe_lite as ref

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HF_KEYS = {
    "model_type": "glm4_moe_lite", "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "hidden_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 4, "q_lora_rank": 12, "kv_lora_rank": 8,
    "qk_nope_head_dim": 12, "qk_rope_head_dim": 4, "v_head_dim": 16,
    "intermediate_size": 48, "moe_intermediate_size": 24, "vocab_size": 67,
    "rms_norm_eps": 1e-5, "rope_theta": 1000000, "rope_scaling": None,
    "attention_bias": False, "partial_rotary_factor": 1,
    "n_routed_experts": 8, "n_shared_experts": 1, "num_experts_per_tok": 3,
    "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 1.8,
    "num_nextn_predict_layers": 1, "tie_word_embeddings": False,
    "max_position_embeddings": 4096,
}
TOL = dict(atol=3e-4, rtol=3e-4)
NORMS = ("ln1", "ln2", "final_ln", "q_a_norm", "kv_a_norm")
DENSE = "full_dense"
T = 29


class _frozen(dict):
    """The HF keys as a static (hashable) argument of ``jax.jit``."""

    def __hash__(self):
        return hash(json.dumps(self, sort_keys=True))


@functools.lru_cache(maxsize=None)
def model(seed=0, scale=0.3):
    """(config, float32 params): init_params with the matrices scaled up
    (so that every latent, rotary part and expert matters), the norm
    weights random around 1 and the choice bias drawn wide enough to
    change choices."""
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
            else:
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
    assert cfg.layer_kinds == (DENSE, FULL, FULL)
    assert cfg.block_counts() == {"full/dense": 1, "full/experts": 2}
    assert cfg.is_hybrid and cfg.has_cacheless_layers
    assert cfg.mla == MLAConfig(12, 8, 12, 4, 16)
    assert cfg.head_dim == 16 and cfg.rotary_dim == 4 and cfg.q_dim == 64
    assert cfg.rotary_base == 1e6 and cfg.rms_norm_eps == 1e-5
    assert not cfg.tie_word_embeddings and cfg.n_nextn_predict_layers == 1
    m = cfg.moe
    assert m.router_score == "sigmoid" and m.aux_loss_coeff == 0
    assert (m.top_k, m.routed_scaling_factor) == (3, 1.8)
    assert m.shared_intermediate_dim == 24 and not m.shared_expert_gate
    assert set(params["layers"]) == {DENSE, FULL}
    full = params["layers"][FULL]
    assert full["wq_a"].shape == (2, 32, 12)
    assert full["wq_b"].shape == (2, 12, 64)
    assert full["wkv_a"].shape == (2, 32, 12)  # [c_kv 8 | k_r 4]
    assert full["wkv_b"].shape == (2, 8, 4 * 28)  # [k_nope 12 | v 16] a head
    assert full["wo"].shape == (2, 64, 32)
    assert full["q_a_norm"].shape == (2, 12)
    assert full["kv_a_norm"].shape == (2, 8)  # the 8 ONLY, not the 12
    assert not {"wq", "wk", "wv"} & set(full)
    assert "w_gate" in params["layers"][DENSE]
    assert "router" not in params["layers"][DENSE]
    assert {"router", "router_bias", "s_gate", "e_gate"} <= set(full)


def test_the_config_goes_out_and_comes_back():
    cfg, _ = model()
    d = hf.hf_config_dict(cfg)
    for key in ("model_type", "q_lora_rank", "kv_lora_rank",
                "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                "first_k_dense_replace", "n_routed_experts",
                "n_shared_experts", "routed_scaling_factor", "topk_method",
                "n_group", "topk_group", "num_nextn_predict_layers"):
        assert d[key] == HF_KEYS[key], key
    assert hf.config_from_hf(types.SimpleNamespace(**d)) == cfg
    share = {**HF_KEYS, "n_routed_experts": 2, "num_routed_experts": 8,
             "expert_shard_count": 4, "expert_shard_index": 3,
             "expert_bias_init_std": 0.005}
    scfg = hf.config_from_hf(types.SimpleNamespace(**share))
    assert (scfg.moe.n_routed, scfg.moe.first_expert) == (8, 6)
    assert scfg.moe.router_bias_init_std == 0.005
    back = hf.hf_config_dict(scfg)
    assert (back["num_routed_experts"], back["expert_shard_index"]) == (8, 3)
    assert back["expert_bias_init_std"] == 0.005
    assert "expert_bias_init_std" not in d


@pytest.mark.parametrize("key,value,name", [
    ("n_group", 2, "n_group"),
    ("topk_group", 2, "topk_group"),
    ("topk_method", "greedy", "topk_method"),
    ("rope_scaling", {"type": "yarn", "factor": 4.0}, "rope_scaling"),
    ("attention_bias", True, "attention_bias"),
    ("partial_rotary_factor", 0.5, "partial_rotary_factor"),
    ("num_key_value_heads", 2, "num_key_value_heads"),
])
def test_keys_of_the_family_that_are_not_built_are_refused_by_name(
        key, value, name):
    with pytest.raises(NotImplementedError, match=name):
        hf.config_from_hf(types.SimpleNamespace(**{**HF_KEYS, key: value}))


@pytest.mark.parametrize("key,value", [("q_lora_rank", None),
                                       ("v_head_dim", 12)])
def test_a_full_rank_query_and_a_narrower_value_are_built(key, value):
    """What was refused by name until PR 63 (``latent_attention_full_rank_
    query``, ``latent_attention_value_width``): the family's model without
    a query latent, or with a value head narrower than its key, runs, and
    the packed forward is finite at the value's width."""
    cfg = hf.config_from_hf(types.SimpleNamespace(**{**HF_KEYS, key: value}))
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    lp = params["layers"][FULL]
    assert ("wq" in lp) == (cfg.mla.q_lora_rank is None)
    assert lp["wo"].shape[1] == 4 * cfg.mla.v_head_dim == cfg.o_dim
    assert bool(jnp.isfinite(system_logits(params, cfg, tokens())).all())


def test_parameter_count_at_the_published_widths():
    """``param_count`` of the benchmark's cut equals the sum of its
    leaves' sizes (shapes only: nothing is allocated) and the number in
    the configuration file; the attention branch's and the experts' sizes
    are ISSUE 58's reckoning."""
    from benchmark import mla_cost, weights

    with open(os.path.join(REPO, "benchmark", "configs",
                           "glm-4.7-flash.json")) as f:
        keys = json.load(f)
    cfg = weights.model_config(keys)
    shapes = jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert n == transformer.param_count(cfg) == keys["n_parameters"]
    assert n == 591_688_192
    assert mla.mla_param_count(cfg.mla, 2048, 20) == 21_759_232
    assert mla_cost.projection_params(keys) == 21_759_232 - 768 - 512
    assert cfg.layer_kinds == (DENSE,) + (FULL,) * 4
    assert cfg.moe.n_routed == 64 and cfg.moe.top_k == 4
    assert shapes["layers"][FULL]["router"].shape == (4, 2048, 64)
    assert shapes["layers"][FULL]["e_gate"].shape == (4, 8, 2048, 1536)
    assert shapes["layers"][FULL]["wkv_b"].shape == (4, 512, 20 * 448)
    assert shapes["lm_head"].shape == (2048, 19456)
    # what a token multiplies through on the share: the benchmark's N
    assert mla_cost.share_params(keys) == 5 * 21_757_952 + 62_914_560 + 4 * (
        131_072 + 9_437_184 + 4_718_592) + 2048 * 19456
    assert 0 < transformer.activated_param_count(cfg) < n


# ---- (b) the whole model against the reference ----

def test_logprobs_match_the_reference():
    cfg, params = model()
    tok = tokens()
    want = jax.jit(ref.token_logprobs, static_argnums=1)(
        params, _frozen(HF_KEYS), tok)
    got = logprobs_of(jnp.asarray(logits_of_the_system()), tok)
    np.testing.assert_allclose(got, want, **TOL)


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
    assert not np.any(got_g[f"layers/{FULL}/router_bias"])
    assert not np.any(want_g[f"layers/{FULL}/router_bias"])


def test_the_ppo_loss_of_the_reference_has_the_surrogates_gradient():
    """Inside the clip range the PPO surrogate's gradient is the
    advantage-weighted logprob gradient."""
    _, params = model()
    keys = {**HF_KEYS, "num_hidden_layers": 2}  # D E: both FFNs
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
    wrong = jax.jit(ref.token_logprobs, static_argnums=(1, 3))(
        params, _frozen(HF_KEYS), tok, frozenset({which}))
    assert float(jnp.abs(got - wrong).max()) > 1e-3, which


# ---- (c) the attention branch alone ----

def hand_rolled(u, lp, pos, seg, eps=1e-5, theta=1e6):
    """The attention branch a head and a token at a time: [T, D] -> [T, D],
    ``k_r`` rotated once and used by every head."""
    n = u.shape[0]
    H, nope, dr, dv, r = 4, 12, 4, 16, 8
    pos, seg = np.asarray(pos), np.asarray(seg)  # static under a trace

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w

    def turn(x, p):  # x [dr], rotate-half at position p
        inv = 1.0 / theta ** (jnp.arange(0, dr, 2) / dr)
        ang = jnp.concatenate([p * inv, p * inv])
        rot = jnp.concatenate([-x[dr // 2:], x[:dr // 2]])
        return x * jnp.cos(ang) + rot * jnp.sin(ang)

    q = (rms(u @ lp["wq_a"], lp["q_a_norm"]) @ lp["wq_b"]).reshape(
        n, H, nope + dr)
    ckv = u @ lp["wkv_a"]
    kv = (rms(ckv[:, :r], lp["kv_a_norm"]) @ lp["wkv_b"]).reshape(
        n, H, nope + dv)
    k_r = jnp.stack([turn(ckv[t, r:], pos[t]) for t in range(n)])
    rows = []
    for t in range(n):
        heads = []
        for h in range(H):
            qt = jnp.concatenate([q[t, h, :nope], turn(q[t, h, nope:],
                                                       pos[t])])
            see = [s for s in range(t + 1) if seg[s] == seg[t] and seg[t] > 0]
            if not see:
                heads.append(jnp.zeros(dv))
                continue
            ks = jnp.stack([jnp.concatenate([kv[s, h, :nope], k_r[s]])
                            for s in see])
            p = jax.nn.softmax(ks @ qt / 4.0)  # (12 + 4) ** -0.5
            heads.append(p @ jnp.stack([kv[s, h, nope:] for s in see]))
        rows.append(jnp.concatenate(heads))
    return jnp.stack(rows) @ lp["wo"]


def attention_branch(cfg, lp, u, seg, pos):
    """The program's attention branch on ``u`` [1, T, D]: ``_block`` of the
    dense kind with the FFN's last matrix zeroed and the block's own norm
    the identity, less the stream."""
    lp = {**lp, "w_down": jnp.zeros_like(lp["w_down"]),
          "ln1": jnp.ones_like(lp["ln1"])}
    cos, sin = transformer.rope_tables(pos, cfg.rotary_dim, cfg.rotary_base)
    return transformer._block(cfg, u, lp, cos, sin, seg, pos, None, None,
                              None, "reference", kind=DENSE)[0] - u


def test_the_branch_against_a_per_head_loop_forward_and_gradients():
    """Forward and every gradient — the rotary columns of ``wkv_a`` take
    the sum over all heads — on a packed row of two documents and padding,
    so that the restarting positions reach the narrow RoPE."""
    cfg, params = model()
    lp = {k: w[0] for k, w in params["layers"][DENSE].items()}
    seg = jnp.asarray([1] * 4 + [2] * 4 + [0])
    pos = jnp.asarray(list(range(4)) + list(range(4)) + [0])
    u = jax.random.normal(jax.random.PRNGKey(4), (9, 32))
    w = jax.random.normal(jax.random.PRNGKey(5), (9, 32)) * (seg > 0)[:, None]
    names = ("wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm", "wkv_b", "wo")

    def got(sub, u):
        return jnp.sum(w * attention_branch(
            cfg, {**lp, **sub}, u[None], seg[None], pos[None])[0])

    def want(sub, u):  # the block's own norm (weight 1) in front
        u = u * jax.lax.rsqrt(jnp.mean(u * u, -1, keepdims=True) + 1e-5)
        return jnp.sum(w * hand_rolled(u, {**lp, **sub}, pos, seg))

    sub = {k: lp[k] for k in names}
    np.testing.assert_allclose(got(sub, u), want(sub, u), rtol=1e-4)
    g = jax.jit(jax.grad(got, argnums=(0, 1)))(sub, u)
    h = jax.jit(jax.grad(want, argnums=(0, 1)))(sub, u)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(h)):
        np.testing.assert_allclose(a, b, atol=2e-4 * float(jnp.abs(b).max()))
    # the shared rotary key's columns are not a head's: all four heads'
    # gradients land in them
    assert float(jnp.abs(g[0]["wkv_a"][:, 8:]).max()) > 0


def test_a_packed_row_of_documents_equals_each_alone():
    cfg, params = model()
    row, seg, pos, docs = packed_row([7, 11, 5], 32)
    got = system_logits(params, cfg, row, seg, pos)[0]
    start = 0
    for doc in docs:
        alone = jax.jit(ref.logits, static_argnums=1)(
            params, _frozen(HF_KEYS), doc)
        np.testing.assert_allclose(got[start:start + len(doc)], alone, **TOL)
        start += len(doc)
    # the row's positions reach the narrow RoPE: a document shifted whole
    # reads the same (RoPE is relative), one stretched does not
    shifted = system_logits(params, cfg, row, seg, pos + 5 * (seg == 2))[0]
    np.testing.assert_allclose(shifted, got, **TOL)
    stretched = system_logits(params, cfg, row, seg, pos * (1 + (seg == 2)))[0]
    assert float(jnp.abs(stretched[7:18] - got[7:18]).max()) > 1e-3
    np.testing.assert_allclose(stretched[:7], got[:7], **TOL)


@pytest.mark.parametrize("entry", ["full", "attention", "matmuls"])
def test_what_the_backward_finds_kept_is_what_a_traced_program_keeps(entry):
    """``remat_kept_bytes`` of the cut's pattern against the residuals jax
    really stacks over the layer scans (the dense block's run and the
    expert blocks'), in bfloat16 with the XLA attention (no kernel's
    output): the two latents with ``k_r`` and both expansions under
    ``matmuls``, never the assembled q and k."""
    from jax._src.ad_checkpoint import saved_residuals

    cfg, _ = model()
    rows, length = 2, 64
    est = transformer.remat_kept_bytes(cfg, rows * length, 2)
    widths = 12 + 64 + 12 + 4 * 28 + 32  # latents + k_r, expansions, wo
    assert est["attention"] == est["full"] == 3 * rows * length * 32 * 2
    assert est["matmuls"] - est["full"] == rows * length * 2 * (
        (widths + 2 * 48) + 2 * (widths + 8 + 2 * 24))
    assert transformer.attention_kept_bytes_per_token(
        cfg, "matmuls", 2, kernel=False) == 2 * widths
    assert transformer.attention_kept_bytes_per_token(
        cfg, "matmuls", 2, kernel=True) == 2 * widths + 4 * (128 * 2 + 4)
    assert transformer.attention_kept_bytes_per_token(
        cfg, "full", 2, kernel=True) == 0
    shapes = jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, jnp.bfloat16), shapes)
    tok = jax.ShapeDtypeStruct((rows, length), jnp.int32)

    def loss(p, tokens, pos, seg):
        y, _ = transformer.forward(p, cfg, tokens, pos, segment_ids=seg,
                                   attn_impl="reference", remat=entry,
                                   return_kv=False, return_hidden=True)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    kept = sum(
        int(np.prod(aval.shape)) * aval.dtype.itemsize
        for aval, src in saved_residuals(loss, params, tok, tok, tok)
        if "output of scan" in src and aval.ndim > 2)
    assert kept == pytest.approx(est[entry], rel=0.05)


# ---- (d) the share, the choice bias and the scaling factor ----

def test_the_parts_all_the_shares_give_add_up_to_the_uncut_layer():
    """The routed parts of the four shares of two experts each, and the
    shared expert counted ONCE, add up to the uncut expert layer — in the
    program and in the reference."""
    cfg, params = model()
    lp = {k: w[0] for k, w in params["layers"][FULL].items()}
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 24, 32))
    whole, aux = moe.moe_mlp(x, lp, cfg.moe)
    assert float(aux["dropped_frac"]) == 0.0
    shared = ref.swiglu(x, lp["s_gate"], lp["s_up"], lp["s_down"])
    parts = []
    for shard in range(4):
        share = dataclasses.replace(
            cfg.moe, num_experts=2, router_experts=8, first_expert=2 * shard)
        held = {**lp, **{k: lp[k][2 * shard:2 * shard + 2]
                         for k in ("e_gate", "e_up", "e_down")}}
        y, _ = moe.moe_mlp(x, held, share)
        parts.append(y - shared)  # every share adds the shared expert whole
        keys = {**HF_KEYS, "n_routed_experts": 2, "num_routed_experts": 8,
                "expert_shard_count": 4, "expert_shard_index": shard}
        np.testing.assert_allclose(y[0], ref.moe(x[0], keys, held), **TOL)
    np.testing.assert_allclose((sum(parts) + shared)[0], whole[0], **TOL)
    np.testing.assert_allclose(ref.moe(x[0], HF_KEYS, lp), whole[0], **TOL)


def test_the_bias_chooses_and_the_factor_scales_the_routed_part_only():
    """The choice is by score + bias, the gates are the chosen SCORES
    renormalised and times 1.8: a bias that lifts one expert into every
    token's choice changes the choice and leaves the other gates' ratios
    alone; the shared expert is not scaled."""
    cfg, params = model()
    lp = {k: w[0] for k, w in params["layers"][FULL].items()}
    x = jax.random.normal(jax.random.PRNGKey(6), (17, 32))
    scores, idx = ref.chosen(x, HF_KEYS, lp)
    _, no_bias = ref.chosen(x, HF_KEYS, lp, frozenset(
        {"bias_left_out_of_choice"}))
    assert np.any(np.sort(idx, -1) != np.sort(no_bias, -1))
    lifted = {**lp, "router_bias": jnp.zeros(8).at[5].set(10.0)}
    g = ref.gates(x, HF_KEYS, lifted)
    assert np.all(g[:, 5] > 0)  # chosen by every token ...
    np.testing.assert_allclose(  # ... at its score's share, not score + 10
        g[:, 5], 1.8 * scores[:, 5] / jnp.sum(jnp.where(g > 0, scores, 0), -1),
        rtol=1e-5)
    np.testing.assert_allclose(jnp.sum(g, -1), 1.8, atol=1e-5)
    got, _ = moe.moe_mlp(x[None], lifted, cfg.moe)
    np.testing.assert_allclose(got[0], ref.moe(x, HF_KEYS, lifted), **TOL)
    # without the factor the routed part shrinks by 1.8 and the shared
    # expert stays: (with - shared) = 1.8 (without - shared)
    plain = dataclasses.replace(cfg.moe, routed_scaling_factor=1.0)
    shared = ref.swiglu(x, lp["s_gate"], lp["s_up"], lp["s_down"])
    unscaled, _ = moe.moe_mlp(x[None], lifted, plain)
    np.testing.assert_allclose(got[0] - shared, 1.8 * (unscaled[0] - shared),
                               atol=1e-4)


def test_the_bias_takes_no_optimizer_update():
    """A step whose gradients are zero moves every decayed weight and
    leaves the choice bias bit for bit (``moe.BUFFER_LEAVES``)."""
    from areal_tpu.api.train_config import OptimizerConfig
    from areal_tpu.backend import jax_train

    _, params = model()
    layers = {"layers": {FULL: {k: params["layers"][FULL][k]
                                for k in ("router", "router_bias")}}}
    tx, _ = jax_train.build_optimizer(
        OptimizerConfig(lr=1e-2, weight_decay=0.1), total_steps=10)
    updates, _ = tx.update(jax.tree.map(jnp.zeros_like, layers),
                           tx.init(layers), layers)
    assert not np.any(np.asarray(updates["layers"][FULL]["router_bias"]))
    assert np.all(np.asarray(updates["layers"][FULL]["router"]) != 0)


# ---- (e) the state dict ----

def test_hf_names_round_trip_with_the_up_projections_head_layout():
    cfg, params = model()
    sd = hf.params_to_hf_state_dict(params, cfg)
    for name, shape in {
        "model.embed_tokens.weight": (67, 32),
        "model.norm.weight": (32,),
        "lm_head.weight": (67, 32),
        "model.layers.0.input_layernorm.weight": (32,),
        "model.layers.0.post_attention_layernorm.weight": (32,),
        "model.layers.0.self_attn.q_a_proj.weight": (12, 32),
        "model.layers.0.self_attn.q_a_layernorm.weight": (12,),
        "model.layers.0.self_attn.q_b_proj.weight": (64, 12),
        "model.layers.0.self_attn.kv_a_proj_with_mqa.weight": (12, 32),
        "model.layers.0.self_attn.kv_a_layernorm.weight": (8,),
        "model.layers.0.self_attn.kv_b_proj.weight": (112, 8),
        "model.layers.0.self_attn.o_proj.weight": (32, 64),
        "model.layers.0.mlp.gate_proj.weight": (48, 32),
        "model.layers.0.mlp.down_proj.weight": (32, 48),
        "model.layers.1.mlp.gate.weight": (8, 32),
        "model.layers.1.mlp.gate.e_score_correction_bias": (8,),
        "model.layers.2.mlp.experts.7.up_proj.weight": (24, 32),
        "model.layers.2.mlp.experts.7.down_proj.weight": (32, 24),
        "model.layers.2.mlp.shared_experts.gate_proj.weight": (24, 32),
    }.items():
        assert sd[name].shape == shape, name
    assert "model.layers.0.mlp.gate.weight" not in sd
    assert "model.layers.1.mlp.gate_proj.weight" not in sd
    assert not any(k.startswith("model.layers.3.") for k in sd)
    # kv_b_proj's rows are by head, [k_nope 12 | v 16] a head: column by
    # column, the program's wkv_b reshaped [8, 4, 28]
    wkv_b = np.asarray(params["layers"][FULL]["wkv_b"])[1].reshape(8, 4, 28)
    rows = sd["model.layers.2.self_attn.kv_b_proj.weight"]
    for h in range(4):
        for j in range(28):
            np.testing.assert_array_equal(rows[h * 28 + j], wkv_b[:, h, j])
    # ... and a head's k_nope / v are what the program's assembly reads
    u = jax.random.normal(jax.random.PRNGKey(8), (1, 5, 32))
    lp = {k: w[1] for k, w in params["layers"][FULL].items()}
    _, k, v = mla.mla_qkv(u, lp, cfg.mla, 4, 1e-5, None, None)
    c_kv = transformer.rms_norm((u @ lp["wkv_a"])[..., :8], lp["kv_a_norm"],
                                1e-5)
    np.testing.assert_allclose(k[0, :, 2, :12], c_kv[0] @ rows[56:68].T,
                               atol=1e-5)
    np.testing.assert_allclose(v[0, :, 2], c_kv[0] @ rows[68:84].T, atol=1e-5)
    # a checkpoint's multi-token-prediction module is skipped by name
    mtp = {f"model.layers.3.{k.split('.', 3)[3]}": w for k, w in sd.items()
           if k.startswith("model.layers.2.")}
    mtp["model.layers.3.eh_proj.weight"] = np.zeros((32, 64), np.float32)
    back = hf.params_from_hf_state_dict({**sd, **mtp}, cfg)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(params)):
        np.testing.assert_array_equal(a, b)


# ---- (f) where the block cannot go yet, and where it goes as it is ----

@pytest.mark.parametrize("where", ["generate", "pipeline", "ring", "tp"])
def test_where_the_block_goes_and_where_it_is_refused_by_name(where):
    cfg, params = model()
    if where == "generate":
        from areal_tpu.models import generate

        assert generate.decode_refusal(cfg).startswith(
            "latent_attention_decode_cache")
        with pytest.raises(NotImplementedError,
                           match="latent_attention_decode_cache"):
            transformer.init_kv_cache(cfg, 1, 8)
        with pytest.raises(NotImplementedError,
                           match="latent_attention_decode_cache"):
            transformer.forward(params, cfg, tokens()[None],
                                jnp.arange(T)[None],
                                segment_ids=jnp.ones((1, T), jnp.int32))
        # ... whatever the FFNs: every block of an all-expert cut too
        flat = hf.config_from_hf(types.SimpleNamespace(
            **{**HF_KEYS, "first_k_dense_replace": 0}))
        assert not flat.is_hybrid and flat.has_cacheless_layers
        assert generate.decode_refusal(flat).startswith(
            "latent_attention_decode_cache")
    elif where == "pipeline":
        from jax.sharding import Mesh

        from areal_tpu.parallel import pipeline

        # dense blocks before expert blocks: a tree per kind is not split
        mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("pp",))
        pipeline._WARNED_FALLBACKS.discard("mixer_layers")
        assert pipeline.pick_pp_microbatches(mesh, cfg, 4) is None
        assert "mixer_layers" in pipeline._WARNED_FALLBACKS
    elif where == "ring":
        from areal_tpu.parallel import ring

        # not refused: a ring passes the EXPANDED k and v (the assembly
        # runs in front of it), 18 x what a ring of the latent would move
        assert ring.ring_refusal(cfg) is None
        # ... and it does, with the up-projections' heads over tp beside
        # it: the forward on a mesh f2 x s2 x t2 is the one device's
        from areal_tpu.parallel import mesh as pmesh
        from areal_tpu.parallel import sharding as psh

        keys = {**HF_KEYS, "vocab_size": 64}  # the embedding's rows over tp
        mcfg = hf.config_from_hf(types.SimpleNamespace(**keys))
        mparams = transformer.init_params(mcfg, jax.random.PRNGKey(0))
        tok = jnp.asarray(np.random.default_rng(0).integers(0, 64, (4, 16)),
                          jnp.int32)
        pos = jnp.broadcast_to(jnp.arange(16), (4, 16))
        seg = jnp.ones((4, 16), jnp.int32)
        want = system_logits(mparams, mcfg, tok, seg, pos)
        mesh = pmesh.make_mesh(pmesh.ParallelSpec.parse("d1f2s2t2"))
        sharded = psh.shard_params(mparams, mesh, mcfg)

        def fwd(p, t, po, s):
            with psh.activation_sharding(mesh):
                return transformer.forward(p, mcfg, t, po, segment_ids=s,
                                           return_kv=False)[0]

        np.testing.assert_allclose(jax.jit(fwd)(sharded, tok, pos, seg), want,
                                   atol=2e-4)
    else:
        from jax.sharding import PartitionSpec as P

        from areal_tpu.parallel.sharding import param_partition_specs

        specs = param_partition_specs(cfg)
        assert jax.tree.structure(jax.tree.map(
            lambda s: 0, specs, is_leaf=lambda x: isinstance(x, P))
        ) == jax.tree.structure(jax.tree.map(lambda a: 0, params))
        for s, a in zip(
                jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)),
                jax.tree.leaves(params)):
            assert len(s) == a.ndim
        full = specs["layers"][FULL]
        # heads over tp as for wq / wo: the up-projections' columns and
        # o_proj's rows are by head; the latents and their norms whole
        assert full["wq_b"][2] == full["wkv_b"][2] == full["wo"][1] == "tp"
        assert full["wq_a"][2] is None and full["wkv_a"][2] is None
        assert full["q_a_norm"] == full["kv_a_norm"] == P(None, None)


# ---- (g) what the benchmark and the operator read ----

def test_the_scopes_and_counts_the_benchmark_reads_are_the_programs():
    from areal_tpu.base import telemetry
    from benchmark import mla_cost, mla_trace

    assert mla_trace.MLA_SCOPES == telemetry.MLA_SCOPES
    cfg, params = model()
    before = dict(mla.geometry_counts())
    text = system_logits.lower(params, cfg, tokens(1, 31)).as_text(
        debug_info=True)
    for scope in telemetry.MLA_SCOPES + (
            "attention", "o_proj", "moe_router", "moe_experts",
            "shared_expert", "mlp"):
        assert scope in text, scope
    assert "qkv_proj" not in text
    # one assembly a run of blocks a program (the dense block's run, the
    # expert blocks' run), at its geometry: what the driver compares
    key = (1, 31) + mla_cost.geometry(HF_KEYS)
    assert key[2:] == (4, 12, 8, 12, 4, 16)
    assert mla.geometry_counts()[key] - before.get(key, 0) == 2
    assert mla_cost.block_runs(HF_KEYS) == 2


def test_the_live_flop_count_counts_the_latents_and_the_dense_block():
    from areal_tpu.base import monitor

    cfg, _ = model()
    d, f, fe = 32, 48, 24
    proj = 2 * (d * 12 + 12 * 64 + d * 12 + 8 * 4 * 28 + 64 * d)
    attn = proj + 2 * 2 * cfg.q_dim * 100.0
    experts = 3 * 3 * 2 * d * fe + 2 * d * 8 + 3 * 2 * d * fe
    want = 3 * attn + 3 * 2 * d * f + 2 * experts + 2 * d * 67
    assert monitor.model_flops_per_token(cfg, 100.0, backward=False) == (
        pytest.approx(want))


def test_what_an_attention_branch_keeps_is_a_gauge_of_the_train_step(
        monkeypatch):
    """``train/mla_kept_bytes_per_token``: what the grid's remat entry
    keeps of one attention branch, set beside the train step's span."""
    from areal_tpu.api.data import MicroBatchSpec, SequenceSample
    from areal_tpu.api.model import FinetuneSpec
    from areal_tpu.api.train_config import OptimizerConfig, TelemetryConfig
    from areal_tpu.backend import jax_train
    from areal_tpu.base import telemetry
    from areal_tpu.system import sentinel

    assert "train/mla_kept_bytes_per_token" in sentinel.METRIC_CATALOG
    cfg, params = model()
    monkeypatch.setattr(jax_train, "choose_remat", lambda kept, b: "matmuls")
    eng = jax_train.JaxTrainEngine(
        cfg, params, OptimizerConfig(type="sgd", lr=1e-2),
        FinetuneSpec(1, 8, 4), compute_dtype="float32", length_bucket=16,
        rows_bucket=1, seqs_bucket=4, remat=True)
    lens = [9, 12, 7, 14]
    rng = np.random.RandomState(0)
    sample = SequenceSample.from_default(
        ids=[f"s{i}" for i in range(len(lens))],
        data={"packed_input_ids": rng.randint(
            2, 67, sum(lens)).astype(np.int32)},
        seqlens=lens)
    tel = telemetry.configure("t", "t", "trainer", 0,
                              TelemetryConfig(enabled=True), push=False)

    def sq_loss(logits, batch):
        w = (batch["segment_ids"] > 0).astype(jnp.float32)
        return jnp.sum(jnp.sum(logits.astype(jnp.float32) ** 2, -1) * w), {
            "n": jnp.sum(w)}

    try:
        eng.train_batch(sample, MicroBatchSpec(max_tokens_per_mb=48),
                        sq_loss, lambda mb: mb.n_tokens)
        snap = tel.registry.snapshot()
    finally:
        telemetry.shutdown()
    assert {p["entry"] for p in eng.remat_plan().values()} == {"matmuls"}
    want = 4 * (12 + 64 + 12 + 4 * 28 + 32)  # float32, no kernel on the CPU
    assert want == transformer.attention_kept_bytes_per_token(
        cfg, "matmuls", 4, False)
    assert snap["gauges"]["train/mla_kept_bytes_per_token"] == want
    spans = [s for s in snap["spans"] if s["name"] == "train/fwd_bwd"]
    assert spans and all(
        s["attrs"]["mla_kept_bytes_per_token"] == want for s in spans)
