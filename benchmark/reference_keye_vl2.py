"""A plain reference of the Keye-VL-2.0 language model (Kwai-Keye,
``model_type`` KeyeVL2): qwen3_moe's blocks with LEARNED SPARSE ATTENTION in
every one. float32 ``jax.numpy`` at ``HIGHEST`` precision, ONE document at
a time, no kernel, no packing, no chunked algebra: the indexer's scores as
a [queries, L] array a block of queries at a time (so that a document of
16k fits the chip; the blocks one after another under ``lax.map`` and the
blocks of the model under ``lax.scan``, so that XLA compiles ONE block of
queries of ONE block of the model and not their product: 120 at this cell's
cut), ``jax.lax.top_k`` a query, attention under the boolean
mask, every held expert on every token. It imports nothing from
``areal_tpu``: it reads the program's parameter pytree by its leaf names.

The layer's equations (``u = input_layernorm(h)``, ``s <= t`` the
document's tokens; what is not a key of ``config.json`` is listed under
``assumed`` in benchmark/configs/keye-vl-2.0-30b-a3b.json, AS RECALLED of
DeepSeek-V3.2-Exp's report where it says so)::

    q = rms_128(u Wq) a head, k = rms_128(u Wk) a head, v = u Wv
    RoPE on all 128 dims at rope_theta (M-RoPE's three streams are equal on
    every token without the vision tower: the one-dimensional rotation)
    qI[t, j] = rope_64(u[t] WqI)[j]                 j = 1..16
    kI[s]    = rope_64(LayerNorm_64(u[s] WkI))      ONE head; weight + bias
    w[t]     = (u[t] Ww) · 16^-1/2 · 64^-1/2
    I[t, s]  = sum_j w[t, j] · relu(qI[t, j] · kI[s])
    S[t]     = all s <= t where t + 1 <= topk, else the topk largest I[t, s]
               (ties: the earlier key — ``lax.top_k``'s order)
    o[t]     = softmax_{s in S[t]}(q[t] · k[s] / sqrt(128)) v[s]
               every query head and key/value head under the same S[t]
    out      = o Wo

and the expert layer: softmax over all routed experts in float32, the
``num_experts_per_tok`` largest, renormalised (``norm_topk_prob``), no
shared expert, no bias; on a SHARE the held experts' part of it
(``num_experts`` of ``num_routed_experts`` from ``expert_shard_index *
num_experts`` on: what the absent ones would add is left out).

Parameters (the program's pytree; ``n`` = blocks): ``embedding`` [V, D],
``final_ln`` [D], ``lm_head`` [D, V]; ``layers``: ``ln1, ln2`` [n, D];
``wq`` [n, D, 32 x 128], ``wk, wv`` [n, D, 4 x 128], ``wo`` [n, 32 x 128,
D], ``q_norm, k_norm`` [n, 128]; ``indexer``: ``wq`` [n, D, 16 x 64],
``wk`` [n, D, 64], ``ww`` [n, D, 16], ``k_norm, k_norm_b`` [n, 64];
``router`` [n, D, E], ``e_gate, e_up`` [n, held, D, Fe], ``e_down`` [n,
held, Fe, D].

``WRONG``: names of WRONG models, for ``check_limits_keye_vl2.py`` and the
parity tests' cases that a tolerance has to refuse.
"""

from __future__ import annotations

from typing import Any, Dict, FrozenSet, Optional

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512
HEAD_BLOCK = 4096
GATE_EPS = 1e-20  # added to the chosen probabilities' sum
LN_EPS = 1e-6  # the indexer key's LayerNorm
WRONG = (
    "relu_left_out",  # I = sum_j w_j (qI_j . kI)
    "weights_ones",  # w replaced by ones (times the two scalings)
    "no_key_layernorm",
    "recent_instead_of_best",  # the topk most recent keys: a window
    "topk_halved",
    "no_selection",  # full causal attention
    "no_indexer_rope",
    "indexer_in_float8",  # qI, kI rounded to float8_e4m3
    "attention_in_float8",  # q, k, v rounded to float8_e4m3
    "matmuls_in_float8",  # every matrix product's operands in float8_e4m3
    "no_qk_norm",
    "gates_not_renormalised",
)
NONE: FrozenSet[str] = frozenset()


def f32(a):
    return jnp.asarray(a, jnp.float32)


def f8(a):
    return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def mm(a, b, wrong: FrozenSet[str] = NONE):
    a, b = f32(a), f32(b)
    if "matmuls_in_float8" in wrong:
        a, b = f8(a), f8(b)
    return jnp.matmul(a, b, precision=HI)


def eps_of(cfg: Dict[str, Any]) -> float:
    return cfg.get("rms_norm_eps", 1e-6)


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * f32(w)


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * f32(w) + f32(b)


def rope(x, theta: float):
    """x [T, H, d]: rotate-half over all of ``d``, positions 0..T-1."""
    T, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None]
    half = d // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


# ---------------- the indexer and the selection ----------------

def top_k_of(cfg: Dict[str, Any], wrong: FrozenSet[str] = NONE) -> int:
    k = int(cfg["sa_config"]["topk"])
    return k // 2 if "topk_halved" in wrong else k


def index_inputs(u, cfg: Dict[str, Any], ip, wrong: FrozenSet[str] = NONE):
    """(qI [T, Hi, Di], kI [T, Di], w [T, Hi])."""
    sa, T = cfg["sa_config"], u.shape[0]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    theta = float(cfg.get("rope_theta", 10000.0))
    qi = mm(u, ip["wq"], wrong).reshape(T, hi, di)
    ki = mm(u, ip["wk"], wrong)
    if "no_key_layernorm" not in wrong:
        ki = layer_norm(ki, ip["k_norm"], ip["k_norm_b"], LN_EPS)
    if "no_indexer_rope" not in wrong:
        qi, ki = rope(qi, theta), rope(ki[:, None], theta)[:, 0]
    w = mm(u, ip["ww"], wrong) * (hi ** -0.5 * di ** -0.5)
    if "weights_ones" in wrong:
        w = jnp.ones_like(w) * (hi ** -0.5 * di ** -0.5)
    if "indexer_in_float8" in wrong:
        qi, ki = f8(qi), f8(ki)
    return qi, ki, w


def scores(qi, ki, w, wrong: FrozenSet[str] = NONE):
    """I [queries, L] of a block of queries against every key."""
    d = jnp.einsum("tjd,sd->tjs", qi, ki, precision=HI)
    if "relu_left_out" not in wrong:
        d = jnp.maximum(d, 0.0)
    return jnp.einsum("tj,tjs->ts", w, d, precision=HI)


def select_block(I, t0, k: int, wrong: FrozenSet[str] = NONE):
    """bool [queries, L]: the keys the queries ``t0 ..`` attend."""
    n, L = I.shape
    pq = t0 + jnp.arange(n)[:, None]
    pk = jnp.arange(L)[None, :]
    causal = pk <= pq
    if "no_selection" in wrong or L <= k:
        return causal
    if "recent_instead_of_best" in wrong:
        return causal & (pq - pk < k)
    _, idx = jax.lax.top_k(jnp.where(causal, I, -jnp.inf), k)
    best = jnp.zeros((n, L), bool).at[jnp.arange(n)[:, None], idx].set(True)
    return best & causal


def by_query_block(fn, *rows):
    """``fn(t0, *blocks)`` [QUERY_BLOCK, ...] over ``rows`` [T, ...] a
    block of queries at a time, one block after another (``lax.map``: one
    block is compiled and one is alive), put together as [T, ...]. The
    last block is filled up with zero rows — queries behind the document's
    end, which attend all of it — and cut off again."""
    T = rows[0].shape[0]
    n = -(-T // QUERY_BLOCK)
    blocks = [jnp.pad(a, [(0, n * QUERY_BLOCK - T)] + [(0, 0)] * (a.ndim - 1)
                      ).reshape(n, QUERY_BLOCK, *a.shape[1:]) for a in rows]
    out = jax.lax.map(lambda x: fn(x[0], *x[1:]),
                      (jnp.arange(n) * QUERY_BLOCK, *blocks))
    return out.reshape(n * QUERY_BLOCK, *out.shape[2:])[:T]


def selection(u, cfg: Dict[str, Any], lp, wrong: FrozenSet[str] = NONE):
    """bool [L, L]: S[t] of every query of the document, a block of
    queries at a time."""
    qi, ki, w = index_inputs(u, cfg, lp["indexer"], wrong)
    k = top_k_of(cfg, wrong)
    return by_query_block(
        lambda t0, qi, w: select_block(scores(qi, ki, w, wrong), t0, k,
                                       wrong), qi, w)


# ---------------- attention under the selection ----------------

def qkv(u, cfg: Dict[str, Any], lp, wrong: FrozenSet[str] = NONE):
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh, T, eps = cfg["head_dim"], u.shape[0], eps_of(cfg)
    theta = float(cfg.get("rope_theta", 10000.0))
    q = mm(u, lp["wq"], wrong).reshape(T, H, dh)
    k = mm(u, lp["wk"], wrong).reshape(T, Hkv, dh)
    v = mm(u, lp["wv"], wrong).reshape(T, Hkv, dh)
    if "no_qk_norm" not in wrong:
        q, k = rms(q, lp["q_norm"], eps), rms(k, lp["k_norm"], eps)
    q, k = rope(q, theta), rope(k, theta)
    if "attention_in_float8" in wrong:
        q, k, v = f8(q), f8(k), f8(v)
    return q, k, v


def attention(u, cfg: Dict[str, Any], lp, wrong: FrozenSet[str] = NONE):
    """The attention branch on ``u`` [T, D], one document: the selection,
    then a masked softmax, a block of queries at a time."""
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    q, k, v = qkv(u, cfg, lp, wrong)
    qi, ki, w = index_inputs(u, cfg, lp["indexer"], wrong)
    kk = top_k_of(cfg, wrong)
    G = H // Hkv

    def block(t0, q, qi, w):
        mask = select_block(scores(qi, ki, w, wrong), t0, kk, wrong)
        qb = q.reshape(-1, Hkv, G, q.shape[-1])
        s = jnp.einsum("tkgd,skd->kgts", qb, k, precision=HI) * (
            q.shape[-1] ** -0.5)
        p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), -1)
        return jnp.einsum("kgts,skd->tkgd", p, v, precision=HI).reshape(
            -1, H * v.shape[-1])

    return mm(by_query_block(block, q, qi, w), lp["wo"], wrong)


# ---------------- the expert layer ----------------

def swiglu(x, w_gate, w_up, w_down, wrong: FrozenSet[str] = NONE):
    return mm(jax.nn.silu(mm(x, w_gate, wrong)) * mm(x, w_up, wrong),
              w_down, wrong)


def gates(x, cfg: Dict[str, Any], lp, wrong: FrozenSet[str] = NONE):
    """[T, D] -> the dense [T, routed] gate matrix: each chosen expert's
    probability over the chosen ones' sum; 0 elsewhere."""
    probs = jax.nn.softmax(mm(x, lp["router"]), -1)
    idx = jnp.argsort(-probs, axis=-1)[:, :cfg["num_experts_per_tok"]]
    top = jnp.take_along_axis(probs, idx, -1)
    if cfg.get("norm_topk_prob", True) and (
            "gates_not_renormalised" not in wrong):
        top = top / (jnp.sum(top, -1, keepdims=True) + GATE_EPS)
    return jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], idx].set(top)


def first_held(cfg: Dict[str, Any]) -> int:
    return int(cfg.get("expert_shard_index", 0) or 0) * cfg["num_experts"]


def moe(x, cfg: Dict[str, Any], lp, wrong: FrozenSet[str] = NONE):
    """The held experts' part of one expert layer on ``x`` [T, D]: every
    held expert on every token, times its gate (0 where the token did not
    choose it)."""
    g = gates(x, cfg, lp, wrong)
    first = first_held(cfg)
    out = jnp.zeros_like(x)
    for e in range(cfg["num_experts"]):
        out = out + g[:, first + e, None] * swiglu(
            x, lp["e_gate"][e], lp["e_up"][e], lp["e_down"][e], wrong)
    return out


# ---------------- the model ----------------

def block(h, cfg: Dict[str, Any], lp, wrong: FrozenSet[str] = NONE):
    eps = eps_of(cfg)
    h = h + attention(rms(h, lp["ln1"], eps), cfg, lp, wrong)
    return h + moe(rms(h, lp["ln2"], eps), cfg, lp, wrong)


def hidden(params, cfg: Dict[str, Any], tokens,
           wrong: FrozenSet[str] = NONE):
    """[T, D]: the residual stream behind the last block (the blocks are
    all alike: one after another under ``lax.scan``)."""
    h = f32(params["embedding"][tokens])
    n = cfg["num_hidden_layers"]
    layers = jax.tree.map(lambda w: w if len(w) == n else w[:n],
                          params["layers"])
    return jax.lax.scan(
        lambda h, lp: (block(h, cfg, lp, wrong), None), h, layers)[0]


def logits(params, cfg: Dict[str, Any], tokens,
           wrong: FrozenSet[str] = NONE) -> jnp.ndarray:
    """[T, V] float32 logits of ONE document ``tokens`` [T]. ``cfg`` holds
    the HF config keys of the configuration file."""
    h = hidden(params, cfg, jnp.asarray(tokens, jnp.int32), wrong)
    return mm(rms(h, params["final_ln"], eps_of(cfg)), params["lm_head"],
              wrong)


def token_logprobs(params, cfg, tokens,
                   wrong: FrozenSet[str] = NONE) -> jnp.ndarray:
    """[T-1]: log p(tokens[t+1] | tokens[:t+1]) for t = 0..T-2 — what the
    PPO actor's inference pass returns for a document; the head a block
    of tokens at a time."""
    tokens = jnp.asarray(tokens, jnp.int32)
    h = rms(hidden(params, cfg, tokens, wrong), params["final_ln"],
            eps_of(cfg))[:-1]
    out = []
    for t0 in range(0, h.shape[0], HEAD_BLOCK):
        lp = jax.nn.log_softmax(
            mm(h[t0:t0 + HEAD_BLOCK], params["lm_head"], wrong), -1)
        out.append(jnp.take_along_axis(
            lp, tokens[1 + t0:1 + t0 + HEAD_BLOCK, None], -1)[:, 0])
    return jnp.concatenate(out)


def loss(params, cfg, tokens, weights: Optional[Any] = None) -> jnp.ndarray:
    """Negative logprob of one document, summed under ``weights`` [T-1]
    or (None) averaged: ``jax.grad`` of it is the gradient tests' oracle
    (the indexer's leaves get exactly zero: a selection carries none)."""
    lp = token_logprobs(params, cfg, tokens)
    if weights is None:
        return -jnp.mean(lp)
    return -jnp.sum(lp * jnp.asarray(weights, jnp.float32))
