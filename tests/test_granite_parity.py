"""Granite 4.0-H (``model_type`` granitemoehybrid, ``num_local_experts`` 0)
through the system against the benchmark's plain reference
(``benchmark/reference_granite_hybrid.py``: float32, the recurrence a
token at a time, attention as a masked softmax, one document at a time) on
seeded weights, on the CPU at a tiny size: hidden 64, 8 query / 4
key-value heads of 8, a Mamba-2 mixer of 8 heads of 16 over ONE B/C group
of 16 states in chunks of 8, an MLP of 96, the published period
``m m m m m a m m m m`` and the family's four multipliers (12, 0.22, a
softmax scale that is NOT 1/sqrt(head), 8).

Both sides compute in float32 here, so they differ by the order of
float32 sums only. A multiplier left at 1, the norm before the gate or a
reset left off move logits by 1e-2 and more.
"""

import dataclasses
import functools
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import hf, ssm, transformer
from areal_tpu.models.config import FULL, SSD, SSMConfig
from areal_tpu.ops import attention
from benchmark import reference_granite_hybrid as ref

PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
HF_KEYS = {
    "model_type": "granitemoehybrid", "num_hidden_layers": 10,
    "layer_types": PERIOD * 4, "hidden_size": 64,
    "num_attention_heads": 8, "num_key_value_heads": 4,
    "intermediate_size": 96, "shared_intermediate_size": 96,
    "vocab_size": 97, "num_local_experts": 0, "num_experts_per_tok": 0,
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_n_groups": 1,
    "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_chunk_size": 8,
    "mamba_expand": 2, "mamba_conv_bias": True, "mamba_proj_bias": False,
    "embedding_multiplier": 12, "residual_multiplier": 0.22,
    "attention_multiplier": 0.25, "logits_scaling": 8,
    "position_embedding_type": "nope", "normalization_function": "rmsnorm",
    "rms_norm_eps": 1e-5, "attention_bias": False, "hidden_act": "silu",
    "tie_word_embeddings": True, "max_position_embeddings": 4096,
}
# the period's first six layers — five Mamba blocks, scanned as one run,
# and the attention block: every kind of block, for what is a property
# of the blocks and not of the period
SIX = {**HF_KEYS, "num_hidden_layers": 6}
TOL = dict(atol=3e-4, rtol=3e-4)
NORMS = ("ln1", "ln2", "final_ln", "norm")
AS_DRAWN = ("conv_w", "dt_bias", "A_log")
JITTERED = ("conv_b", "D")


def model(keys=HF_KEYS, seed=0, scale=0.3):
    """(config, float32 params): init_params with the matrices scaled up
    (so that every mixer matters; the embedding stays as drawn: it is
    read times 12), the norm weights random around 1, the convolution's
    bias and the skip ``D`` random, the decay's parameters as drawn.
    Built once a set of keys: no test writes into the tree it gets."""
    return _model(json.dumps(keys, sort_keys=True), seed, scale)


@functools.lru_cache(maxsize=None)
def _model(keys, seed, scale):
    cfg = hf.config_from_hf(types.SimpleNamespace(**json.loads(keys)))
    flat = hf.flatten_pytree(
        transformer.init_params(cfg, jax.random.PRNGKey(seed)))
    rngs = jax.random.split(jax.random.PRNGKey(seed + 1), len(flat))
    for (name, x), k in zip(sorted(flat.items()), rngs):
        leaf = name.split("/")[-1]
        if leaf in NORMS:
            flat[name] = 1.0 + 0.1 * jax.random.normal(k, x.shape)
        elif leaf in JITTERED:
            flat[name] = x + 0.1 * jax.random.normal(k, x.shape)
        elif leaf == "embedding":
            flat[name] = x * 4.0
        elif leaf not in AS_DRAWN:
            flat[name] = x * (scale / 0.02)
    return cfg, hf.unflatten_pytree(flat)


def tokens(seed=0, T=43):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(2, HF_KEYS["vocab_size"], T), jnp.int32)


def packed_row(lens, T, seed=10):
    """(row [1, T], segment ids [1, T], the documents) of documents of
    ``lens`` tokens packed one behind another, then padding."""
    docs = [tokens(seed + i, n) for i, n in enumerate(lens)]
    pad = T - sum(lens)
    row = jnp.concatenate(docs + [jnp.zeros(pad, jnp.int32)])[None]
    seg = jnp.asarray([sum(([i + 1] * n for i, n in enumerate(lens)), [])
                       + [0] * pad], jnp.int32)
    return row, seg, docs


def system_logits(params, cfg, tok, seg=None, remat=False):
    """Logits of a packed grid ``tok`` [B, T] (or one document [T])."""
    one = tok.ndim == 1
    if one:
        tok = tok[None]
    B, T = tok.shape
    seg = jnp.ones((B, T), jnp.int32) if seg is None else seg
    out, _ = transformer.forward(
        params, cfg, tok, jnp.broadcast_to(jnp.arange(T), (B, T)),
        segment_ids=seg, attn_impl="reference", return_kv=False, remat=remat)
    return out[0] if one else out


def mean_logprob(logits, tok):
    lp = jax.nn.log_softmax(logits[:-1], -1)
    return jnp.mean(jnp.take_along_axis(lp, tok[1:, None], -1))


# ---- (a) the family ----

def test_the_family_reads_the_blocks_and_the_multipliers():
    cfg, params = model()
    assert cfg.layer_kinds == (SSD,) * 5 + (FULL,) + (SSD,) * 4
    assert cfg.period_kinds == cfg.layer_kinds
    assert cfg.is_hybrid and cfg.pos_embedding == "none"
    assert not cfg.has_mixer_layers and cfg.has_cacheless_layers
    assert cfg.moe is None and cfg.tie_word_embeddings
    assert cfg.ssm == SSMConfig(n_heads=8, head_dim=16, n_groups=1,
                                state_dim=16, conv_kernel=4, chunk_size=8)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling) == (
                12.0, 0.22, 0.25, 8.0)
    assert cfg.intermediate_dim == 96 and cfg.head_dim == 8
    assert cfg.block_counts() == {"ssd/dense": 9, "full/dense": 1}
    assert cfg.attention_windows() == {None: 1}
    assert {k: v["ln1"].shape[0] for k, v in params["layers"].items()} == {
        SSD: 9, FULL: 1}
    assert "ln" not in params["layers"][SSD]  # the block's own two norms
    assert sorted(set(params["layers"][SSD]) - set(params["layers"][FULL])
                  ) == ["A_log", "D", "conv_b", "conv_w", "dt_bias",
                        "in_proj", "norm", "out_proj"]
    again = hf.config_from_hf(types.SimpleNamespace(**hf.hf_config_dict(cfg)))
    assert again == cfg
    # two periods of the published 40 layers are two scan steps
    two = hf.config_from_hf(types.SimpleNamespace(
        **{**HF_KEYS, "num_hidden_layers": 20}))
    assert two.period_kinds == cfg.layer_kinds and two.n_layers == 20
    # a cut that starts inside the published stack (the benchmark's: at
    # the period's attention layer, so that the Mamba blocks are ONE run)
    cut = hf.config_from_hf(types.SimpleNamespace(
        **{**HF_KEYS, "first_layer_index": 5}))
    assert cut.layer_kinds == (FULL,) + (SSD,) * 9
    assert transformer.period_runs(cut.period_kinds) == (
        ((FULL,), 1), ((SSD,), 9))


def test_every_other_family_computes_what_it_computed():
    """The multipliers default to the identity: no family but this one
    sets them, and ``_residual`` is then the plain sum."""
    from areal_tpu.models.config import tiny_config

    cfg = tiny_config()
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling) == (
                1.0, 1.0, None, 1.0)
    h, b = jnp.ones((1, 2, 4)), jnp.full((1, 2, 4), 0.5)
    jaxpr = jax.make_jaxpr(lambda h, b: transformer._residual(cfg, h, b))(h, b)
    names = [e.primitive.name for e in jaxpr.eqns]
    assert "add" in names and "mul" not in names


def test_parameter_count_at_the_published_widths():
    """The issue's arithmetic: a Mamba block 76.2 M, an attention block
    60.8 M, the tied embedding 205.5 M, the model 3.19 B; the cell's cut
    (heads by 2, the vocabulary by 8, one period) 653.0 M, its mixer
    13.19 M, its attention 5.24 M, the MLP 50.33 M."""
    keys = {**HF_KEYS, "num_hidden_layers": 40, "hidden_size": 2048,
            "num_attention_heads": 32, "num_key_value_heads": 8,
            "intermediate_size": 8192, "shared_intermediate_size": 8192,
            "vocab_size": 100352, "mamba_n_heads": 64, "mamba_d_head": 64,
            "mamba_d_state": 128, "mamba_chunk_size": 256,
            "attention_multiplier": 0.015625}
    cfg = hf.config_from_hf(types.SimpleNamespace(**keys))
    count = transformer._block_param_count
    assert cfg.head_dim == 64
    assert round(count(cfg, False, SSD) / 1e5) == 762
    assert round(count(cfg, False, FULL) / 1e5) == 608
    assert round(transformer.param_count(cfg) / 1e7) == 319
    cut = hf.config_from_hf(types.SimpleNamespace(**{
        **keys, "num_hidden_layers": 10, "mamba_n_heads": 32,
        "num_attention_heads": 16, "num_key_value_heads": 4,
        "vocab_size": 12544, "head_dim": 64}))
    mlp = 3 * 2048 * 8192 + 2 * 2048
    assert round((count(cut, False, SSD) - mlp) / 1e4) == 1319
    assert round((count(cut, False, FULL) - mlp) / 1e4) == 524
    assert round(transformer.param_count(cut) / 1e5) == 6530
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(jax.eval_shape(
        lambda: transformer.init_params(cut, jax.random.PRNGKey(0)))))
    assert n == transformer.param_count(cut)


# ---- (b) the program against the reference ----

@pytest.mark.parametrize("first", [0, 5])
def test_logits_match_the_reference(first):
    """The published layers 0-9 (m x5 . a . m x4: the Mamba stack cut
    into two scanned runs) and the benchmark's cut 5-14 (a . m x9)."""
    keys = {**HF_KEYS, "first_layer_index": first}
    cfg, params = model(keys)
    tok = tokens()  # 43 tokens: five chunks and a part of one
    np.testing.assert_allclose(system_logits(params, cfg, tok),
                               ref.logits(params, keys, tok), **TOL)


@pytest.mark.parametrize("chunk", [None, 16])
def test_the_engines_ppo_logprobs_match_the_reference_a_document(chunk):
    """What ``actor_inf`` returns, through the engine's (chunked) head, on
    packed rows of 2 to 4 documents whose boundaries fall INSIDE chunks of
    the scan (8 tokens): each document's logprobs are the reference's of
    that document alone."""
    from areal_tpu.backend.jax_train import JaxTrainEngine

    cfg, params = model(SIX)
    T = 64
    rows = [packed_row(lens, T, seed) for lens, seed in (
        ((21, 37), 20), ((11, 14, 10, 19), 30), ((30, 5, 27), 40))]
    batch = {
        "tokens": jnp.concatenate([r[0] for r in rows]),
        "segment_ids": jnp.concatenate([r[1] for r in rows]),
        "positions": jnp.broadcast_to(jnp.arange(T), (3, T)),
    }
    eng = JaxTrainEngine(cfg, params, compute_dtype="float32",
                         logprob_chunk=chunk)
    got, _ = eng._forward_token_logprobs(eng.params, batch)
    alone = jax.jit(lambda p, doc: ref.token_logprobs(p, SIX, doc))
    for r, (_, seg, docs) in enumerate(rows):
        col = 0
        for doc in docs:
            n = len(doc)
            np.testing.assert_allclose(
                got[r, col + 1:col + n], alone(params, doc), **TOL)
            assert float(got[r, col]) == 0.0  # a document's first token
            col += n


def test_loss_and_every_gradient_match_the_reference():
    cfg, params = model()
    tok = tokens(1)
    got_l, got_g = jax.jit(jax.value_and_grad(
        lambda p: mean_logprob(system_logits(p, cfg, tok, remat="full"),
                               tok)))(params)
    want_l, want_g = jax.jit(jax.value_and_grad(
        lambda p: -ref.loss(p, HF_KEYS, tok)))(params)
    np.testing.assert_allclose(got_l, want_l, atol=1e-5, rtol=1e-5)
    got, want = hf.flatten_pytree(got_g), hf.flatten_pytree(want_g)
    assert sorted(got) == sorted(want)
    for name in sorted(got):
        scale = float(jnp.abs(want[name]).max())
        assert scale > 1e-6, name  # every leaf matters
        np.testing.assert_allclose(got[name], want[name],
                                   atol=2e-3 * scale + 1e-7, rtol=2e-3,
                                   err_msg=name)


def test_the_tied_embeddings_gradient_is_the_sum_of_its_two_uses():
    """``E`` is read times 12 going in and over 8 coming out: its gradient
    is the sum of the gradient through each use alone."""
    cfg, params = model(SIX)
    tok = tokens(2, 29)
    untied = dataclasses.replace(cfg, tie_word_embeddings=False)

    def loss(p):
        return mean_logprob(system_logits(p, untied, tok), tok)

    both = jax.jit(jax.grad(
        lambda p: mean_logprob(system_logits(p, cfg, tok), tok)))(
            params)["embedding"]
    g = jax.jit(jax.grad(loss))({**params, "lm_head": params["embedding"].T})
    assert float(jnp.abs(g["embedding"]).max()) > 1e-6
    assert float(jnp.abs(g["lm_head"]).max()) > 1e-6
    np.testing.assert_allclose(both, g["embedding"] + g["lm_head"].T,
                               rtol=1e-4, atol=1e-7)
    want = jax.jit(jax.grad(lambda p: -ref.loss(p, SIX, tok)))(
        params)["embedding"]
    np.testing.assert_allclose(both, want, rtol=2e-3, atol=2e-3 * float(
        jnp.abs(want).max()))


WRONG_KEYS = {
    "embedding_multiplier_1": {"embedding_multiplier": 1.0},
    "residual_multiplier_1": {"residual_multiplier": 1.0},
    "softmax_scale_of_the_head": {"attention_multiplier": 8 ** -0.5},
    "logits_scaling_1": {"logits_scaling": 1.0},
}


@pytest.mark.parametrize("which", sorted(WRONG_KEYS) + ["norm_before_gate"])
def test_a_wrong_reference_is_told_apart(which, monkeypatch):
    cfg, params = model()
    tok = tokens()
    keys = {**HF_KEYS, **WRONG_KEYS.get(which, {})}
    if which == "norm_before_gate":
        def gated_norm(y, z, w, groups, eps, sum_sq=None, width=None):
            y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
            return y * w * jax.nn.silu(z)
        monkeypatch.setattr(ref, "gated_norm", gated_norm)
    got = system_logits(params, cfg, tok)
    assert float(jnp.abs(got - ref.logits(params, keys, tok)).max()) > 1e-2


# ---- (c) packed rows ----

@pytest.mark.parametrize("lens", [(21, 37), (11, 14, 10, 19), (30, 5, 27)])
def test_a_document_behind_others_equals_the_document_alone(lens):
    """2 to 4 documents a row, every boundary inside a chunk of 8: the
    scan, the convolution and attention stop at it."""
    cfg, params = model(SIX)
    assert all(sum(lens[:i]) % cfg.ssm.chunk_size for i in range(1, len(lens)))
    row, seg, docs = packed_row(lens, 64)
    packed = system_logits(params, cfg, row, seg)[0]
    col = 0
    for doc in docs:
        np.testing.assert_allclose(packed[col:col + len(doc)],
                                   ref.logits(params, SIX, doc), **TOL)
        col += len(doc)
    # and with the boundaries left off it is another model
    merged = system_logits(params, cfg, row, (seg > 0).astype(jnp.int32))[0]
    a, b = len(docs[0]), sum(lens)
    assert float(jnp.abs(merged[a:b] - packed[a:b]).max()) > 1e-2


# ---- (d) one scan a run of the period ----

def unrolled(cfg, params, h, seg):
    """The layers one by one through ``_block``."""
    seen = {}
    pos = jnp.broadcast_to(jnp.arange(h.shape[1]), h.shape[:2])
    for kind in cfg.layer_kinds:
        j = seen.get(kind, 0)
        seen[kind] = j + 1
        lp = {k: w[j] for k, w in params["layers"][kind].items()}
        h, _, _ = transformer._block(cfg, h, lp, None, None, seg, pos, None,
                                     None, None, "reference", kind=kind)
    return h


def weigh(out):
    return jnp.sum(out * jnp.cos(jnp.arange(out.size).reshape(out.shape)))


@functools.lru_cache(maxsize=None)
def two_periods():
    """Two periods of the pattern, a row of two documents, and the
    unrolled layers' loss and gradients on it (once for every ``remat``)."""
    cfg, params = model({**HF_KEYS, "num_hidden_layers": 20})
    h0 = params["embedding"][tokens(4, 64)][None]
    seg = jnp.asarray([[1] * 30 + [2] * 34], jnp.int32)
    want = jax.jit(jax.value_and_grad(
        lambda p, h: weigh(unrolled(cfg, p, h, seg)), argnums=(0, 1)))(
            params, h0)
    return cfg, params, h0, seg, want


@pytest.mark.parametrize("remat", [False, "full", "matmuls"])
def test_the_scanned_runs_equal_the_unrolled_layers(remat):
    cfg, params, h0, seg, (want_l, want_g) = two_periods()
    pos = jnp.arange(64)[None]

    def scanned(p, h):
        return weigh(transformer.apply_layer_stack(
            cfg, h, p["layers"], None, None, seg, pos,
            attn_impl="reference", remat=remat)[0])

    got_l, got_g = jax.jit(jax.value_and_grad(scanned, argnums=(0, 1)))(
        params, h0)
    np.testing.assert_allclose(got_l, want_l, rtol=1e-4)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got_g),
                            jax.tree.leaves(want_g)):
        scale = float(jnp.abs(w).max()) + 1e-9
        np.testing.assert_allclose(g, w, atol=2e-3 * scale, rtol=2e-3,
                                   err_msg=jax.tree_util.keystr(path))


def test_the_period_traces_one_copy_of_each_run():
    """``m x5 . a . m x4`` is three runs: the program holds TWO chunked
    scans for nine Mamba blocks, and one attention call."""
    cfg, params = model()
    assert transformer.period_runs(cfg.period_kinds) == (
        ((SSD,), 5), ((FULL,), 1), ((SSD,), 4))
    h0 = params["embedding"][tokens(5, 64)][None]
    seg = jnp.ones((1, 64), jnp.int32)
    scans = dict(ssm.geometry_counts())
    with attention.dispatch_label("granite-runs"):
        jax.make_jaxpr(lambda p, h: transformer.apply_layer_stack(
            cfg, h, p["layers"], None, None, seg, jnp.arange(64)[None],
            attn_impl="reference")[0])(params, h0)
    key = (1, 64, 8, 8, 1)  # rows, length, chunk, heads, groups
    assert ssm.geometry_counts()[key] - scans.get(key, 0) == 2
    assert attention.dispatch_counts()["granite-runs"] == {"reference": 1}


def test_what_the_backward_finds_kept_counts_the_mixers_two_projections():
    cfg, _ = model()
    kept = transformer.remat_kept_bytes(cfg, tokens=64, itemsize=2)
    assert kept["full"] == kept["attention"] == 10 * 64 * 64 * 2
    mlp = 2 * 96
    widths = 9 * (cfg.ssm.in_proj_dim + 64 + mlp) + (
        64 + 2 * 32 + 64 + mlp)
    assert kept["matmuls"] - kept["attention"] == 64 * 2 * widths
    assert transformer._block_matmul_widths(cfg, False, SSD) == (
        cfg.ssm.in_proj_dim + 64 + mlp)


# ---- (e) the share: heads by 2 ----

def mamba_share(lp, s, H, P, GN):
    """Share ``s`` of 2 of a Mamba-2 mixer's leaves: its half of the
    heads' channels of z, x, dt, the per-head vectors, the norm's weight
    and ``out_proj``'s rows; B and C (the one group) whole, as a
    deployment replicates them."""
    di, h = H * P, H // 2
    ch = slice(s * h * P, (s + 1) * h * P)  # this share's channels
    hd = slice(s * h, (s + 1) * h)  # and heads
    z, x, bc, dt = jnp.split(lp["in_proj"], [di, 2 * di, 2 * di + 2 * GN], -1)
    cx, cbc = lp["conv_w"][:, :di], lp["conv_w"][:, di:]
    return {
        "in_proj": jnp.concatenate([z[:, ch], x[:, ch], bc, dt[:, hd]], -1),
        "conv_w": jnp.concatenate([cx[:, ch], cbc], -1),
        "conv_b": jnp.concatenate([lp["conv_b"][:di][ch],
                                   lp["conv_b"][di:]]),
        "dt_bias": lp["dt_bias"][hd], "A_log": lp["A_log"][hd],
        "D": lp["D"][hd], "norm": lp["norm"][ch],
        "out_proj": lp["out_proj"][ch],
    }


def test_two_head_shares_of_a_mamba_mixer_add_up_to_the_uncut_layer():
    """What the PROGRAM computes on each of two head shares — its heads'
    part of ``out_proj``'s sum, the gated norm over ITS channels — adds up
    to the uncut reference's layer once each share's statistic is
    replaced by the sum of squares over both (a per-token factor, since
    ``out_proj`` is linear): the one all-reduce a deployment adds."""
    cfg, params = model()
    lp = {k: w[0] for k, w in params["layers"][SSD].items()}
    H, P, N = 8, 16, 16
    u = jax.random.normal(jax.random.PRNGKey(3), (37, 64))
    whole = ref.mamba(u, HF_KEYS, lp)
    half = {**HF_KEYS, "mamba_n_heads": H // 2}
    shares = [mamba_share(lp, s, H, P, N) for s in (0, 1)]
    eps = HF_KEYS["rms_norm_eps"]
    sq = []
    for sp in shares:
        y, z = ref.mamba_gated(u, half, sp)
        sq.append(jnp.sum((y * jax.nn.silu(z)) ** 2, -1, keepdims=True))
    total = 0.0
    for sp, own in zip(shares, sq):
        part = ssm.mamba_mixer(u[None], sp, dataclasses.replace(
            cfg.ssm, n_heads=H // 2), eps, None)[0]
        # alone, the share's norm is over its own 64 channels
        np.testing.assert_allclose(part, ref.mamba(u, half, sp), **TOL)
        total = total + part * jnp.sqrt(
            (own / (H * P // 2) + eps) / ((sq[0] + sq[1]) / (H * P) + eps))
        # the reference's own share form says the same
        np.testing.assert_allclose(
            part * jnp.sqrt((own / (H * P // 2) + eps)
                            / ((sq[0] + sq[1]) / (H * P) + eps)),
            ref.mamba(u, half, sp, sum_sq=sq[0] + sq[1], width=H * P), **TOL)
    np.testing.assert_allclose(total, whole, **TOL)
    assert float(jnp.abs(whole).max()) > 0.1


def test_two_head_shares_of_attention_add_up_to_the_uncut_layer():
    cfg, params = model()
    lp = {k: w[0] for k, w in params["layers"][FULL].items()}
    h = jax.random.normal(jax.random.PRNGKey(4), (1, 29, 64))
    eps = HF_KEYS["rms_norm_eps"]
    whole = ref.attention(ref._rms(h[0], lp["ln1"], eps), HF_KEYS, lp)
    half = dataclasses.replace(cfg, n_q_heads=4, n_kv_heads=2,
                               residual_multiplier=1.0)
    total = 0.0
    for s in (0, 1):
        q, kv = slice(s * 32, (s + 1) * 32), slice(s * 16, (s + 1) * 16)
        sp = {**lp, "wq": lp["wq"][:, q], "wk": lp["wk"][:, kv],
              "wv": lp["wv"][:, kv], "wo": lp["wo"][q],
              "w_down": jnp.zeros_like(lp["w_down"])}
        out, _, _ = transformer._block(
            half, h, sp, None, None, jnp.ones((1, 29), jnp.int32),
            jnp.arange(29)[None], None, None, None, "reference", kind=FULL)
        total = total + (out - h)[0]
    np.testing.assert_allclose(total, whole, **TOL)
    assert float(jnp.abs(whole).max()) > 0.1


# ---- (f) names, scopes, gauges and the refusals by name ----

def test_hf_names_round_trip():
    cfg, params = model()
    sd = hf.params_to_hf_state_dict(params, cfg)
    fused = sd["model.layers.0.shared_mlp.input_linear.weight"]
    assert fused.shape == (2 * 96, 64)  # [gate | up] rows
    np.testing.assert_array_equal(
        fused[:96], np.asarray(params["layers"][SSD]["w_gate"][0]).T)
    assert sd["model.layers.0.shared_mlp.output_linear.weight"].shape == (
        64, 96)
    assert sd["model.layers.0.mamba.in_proj.weight"].shape == (
        cfg.ssm.in_proj_dim, 64)
    assert sd["model.layers.0.mamba.conv1d.weight"].shape == (
        cfg.ssm.conv_dim, 1, 4)
    assert sd["model.layers.0.mamba.A_log"].shape == (8,)
    assert sd["model.layers.0.mamba.norm.weight"].shape == (128,)
    assert sd["model.layers.5.self_attn.k_proj.weight"].shape == (32, 64)
    assert "model.layers.5.mamba.in_proj.weight" not in sd
    assert "lm_head.weight" not in sd  # tied
    back = hf.params_from_hf_state_dict(sd, cfg)
    want, got = hf.flatten_pytree(params), hf.flatten_pytree(back)
    assert sorted(want) == sorted(got)
    for name in want:
        np.testing.assert_array_equal(np.asarray(want[name]),
                                      np.asarray(got[name]), err_msg=name)


def test_the_familys_expert_siblings_are_refused_by_name():
    with pytest.raises(NotImplementedError,
                       match="expert_layers_beside_shared_mlp"):
        hf.config_from_hf(types.SimpleNamespace(
            **{**HF_KEYS, "num_local_experts": 64, "num_experts_per_tok": 6}))
    with pytest.raises(NotImplementedError, match="layer_types"):
        hf.config_from_hf(types.SimpleNamespace(
            **{**HF_KEYS, "layer_types": ["mamba", "moe"] * 5}))


@pytest.mark.parametrize("where", ["ring", "pipeline", "generate"])
def test_where_the_block_cannot_go_yet_is_refused_by_name(where):
    cfg, params = model()
    if where == "ring":
        from areal_tpu.parallel import ring

        assert ring.ring_refusal(cfg) == "state_space_scan"
    elif where == "pipeline":
        from jax.sharding import Mesh

        from areal_tpu.parallel import pipeline

        mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("pp",))
        pipeline._WARNED_FALLBACKS.discard("mixer_layers")
        assert pipeline.pick_pp_microbatches(mesh, cfg, 4) is None
        assert "mixer_layers" in pipeline._WARNED_FALLBACKS
    else:
        from areal_tpu.models import generate

        assert generate.decode_refusal(cfg).startswith(
            "recurrent_decode_state")
        with pytest.raises(NotImplementedError, match="recurrent_decode_state"):
            transformer.init_kv_cache(cfg, 1, 8)
        with pytest.raises(NotImplementedError, match="recurrent_decode_state"):
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


def test_the_scopes_the_benchmark_reads_are_the_programs():
    from areal_tpu.base import telemetry
    from benchmark import granite_trace

    assert set(granite_trace.SCOPES) == set(telemetry.SSM_SCOPES)
    cfg, params = model()
    text = jax.jit(lambda p, t: system_logits(p, cfg, t)).lower(
        params, tokens()).as_text(debug_info=True)
    for scope in telemetry.SSM_SCOPES + ("mlp", "attention", "o_proj"):
        assert scope in text, scope


# A Granite-like program at the widths the scan's kernel takes: 2 heads of
# 64 over one group of 128 states, chunks of 128, two blocks.
KERNEL_KEYS = {**HF_KEYS, "num_hidden_layers": 2,
               "layer_types": ["mamba", "mamba"], "mamba_n_heads": 2,
               "mamba_d_head": 64, "mamba_d_state": 128,
               "mamba_chunk_size": 128}


def kernel_width_grad(cfg, params, tok, seg, impl):
    """The jitted gradient of Σ logits² of a packed grid under ``impl``."""
    B, T = tok.shape
    pos = jnp.broadcast_to(jnp.arange(T), (B, T))

    def loss(p):
        out, _ = transformer.forward(p, cfg, tok, pos, segment_ids=seg,
                                     attn_impl=impl, return_kv=False,
                                     remat="full")
        return jnp.sum(out.astype(jnp.float32) ** 2), out

    return jax.jit(jax.value_and_grad(loss, has_aux=True))


def test_the_scans_kernel_runs_the_blocks_and_keeps_the_geometry_key():
    """The same program through the Pallas kernel (interpreted) and
    through the XLA einsums: equal logits and gradients; ``geometry_
    counts()`` counts the same scans under the same five-field key with
    the TRUE row length (150: no multiple of the chunk) — the benchmark's
    ``kernel_ok`` and roofline read it — and ``scan_impl_counts()`` alone
    tells the two apart."""
    cfg, params = model(KERNEL_KEYS)
    row, seg, _ = packed_row([61, 40, 37], 150)
    key = (1, 150, 128, 2, 1)
    seen = {}
    for impl, how in (("pallas_interpret", "pallas_interpret"),
                      ("reference", "xla")):
        geom = ssm.geometry_counts().get(key, 0)
        hows = ssm.scan_impl_counts().get(how, 0)
        (_, logits), grads = kernel_width_grad(cfg, params, row, seg, impl)(
            params)
        # one scanned run of blocks, traced as often under either form
        traced = ssm.geometry_counts()[key] - geom
        assert traced >= 1 and traced == seen.get("traced", traced)
        assert ssm.scan_impl_counts()[how] - hows == traced
        seen["traced"] = traced
        seen[impl] = (logits, grads)
    assert all(len(k) == 5 for k in ssm.geometry_counts())
    np.testing.assert_allclose(seen["pallas_interpret"][0],
                               seen["reference"][0], **TOL)
    flat = [jax.tree.leaves(seen[i][1]) for i in ("pallas_interpret",
                                                  "reference")]
    for got, want in zip(*flat):
        np.testing.assert_allclose(
            got, want, atol=3e-4 * float(jnp.max(jnp.abs(want))) + 1e-6,
            rtol=3e-3)


def test_the_scans_kernels_sit_under_the_scope_the_benchmark_reads():
    """Lowered for a TPU, a block's forward kernel, the one its backward
    re-runs and the backward kernel are all filed under ``ssm_scan`` by
    the benchmark's own reduction (``granite_scan_busy_pct`` /
    ``granite_scan_roofline``), none elsewhere."""
    import re

    from benchmark import granite_trace, ssm_trace
    from areal_tpu.ops.pallas import ssd_scan

    assert granite_trace.SCOPES == ssm_trace.SSM_SCOPES
    cfg, params = model(KERNEL_KEYS)
    row, seg, _ = packed_row([150, 106], 256)
    text = kernel_width_grad(cfg, params, row, seg, "pallas").trace(
        params).lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
    names = re.findall(r'loc\("([^"]*pallas_call)"', text)
    for kernel_name in (ssd_scan.FWD_NAME, ssd_scan.BWD_NAME):
        mine = [n for n in names if f"/{kernel_name}/" in n]
        assert mine, (kernel_name, names)
        assert {ssm_trace.scope_of(n) for n in mine} == {"ssm_scan"}, mine


def test_documents_per_row_is_a_gauge_of_the_train_step():
    """``train/docs_per_row``: documents over the rows that hold any, of
    the packed grids of one train batch."""
    from areal_tpu.api.data import MicroBatchSpec, SequenceSample
    from areal_tpu.api.train_config import TelemetryConfig
    from areal_tpu.backend.jax_train import JaxTrainEngine
    from areal_tpu.base import telemetry

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
        got = telemetry.get().snapshot()["gauges"]["train/docs_per_row"]
    finally:
        telemetry.shutdown()
    rows = sum(len({r for r, _ in mb.layout.placements}) for mb in ub.mbs)
    assert got == pytest.approx(len(lens) / rows)
    assert 1.0 < got <= 8.0
