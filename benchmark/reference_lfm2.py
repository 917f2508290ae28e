"""Plain reference: the forward pass, the PPO loss and (through
``jax.grad``) its gradients of an LFM2-MoE model (LiquidAI, ``model_type``
``lfm2_moe``) in straightforward float32 ``jax.numpy`` at
``precision="highest"`` — a Python loop over layers and over the held
experts, the convolution as three shifted products of ONE document, a
masked softmax, no kernel, no cache, no scan, no sorting, no packing, and
no import from ``areal_tpu``. Written from the published ``config.json``
and HF's ``modeling_lfm2_moe.py`` AS RECALLED (each equation is listed
under ``assumed`` in the configuration's file). ``h`` [T, D]::

    h = E[token]
    block l, ``layer_types[l]`` conv or full_attention:
      u = rms(h, operator_norm)                  x / sqrt(mean x² + norm_eps) · w
      conv:  [B | C | x] = u W_in                three chunks of D, no bias
             z = B ⊙ x
             c_t = Σ_j w_j ⊙ z_{t-(K-1)+j}       K = conv_L_cache taps; w_{K-1} on
                                                 the token itself; no bias, NO
                                                 activation; a tap before the
                                                 document's first token reads 0
             m = (C ⊙ c) W_out
      full:  q = rms_head(u Wq)  k = rms_head(u Wk)  v = u Wv     heads of D / Hq
             q, k = rope(q), rope(k)             rotate-half, theta, all of the head
             m = softmax(causal(q kᵀ / sqrt(Dh))) v Wo       Hq heads on Hkv
      h += m
      u = rms(h, ffn_norm)
      l <  num_dense_layers:  f = (silu(u W1) ⊙ (u W3)) W2
      l >= num_dense_layers:  s = sigmoid_f32(u Wr)  over the routed experts
          chosen = top_k of s + expert_bias      (the bias chooses only)
          g = s on the chosen;  g /= sum(g) + 1e-6  (norm_topk_prob)
          g *= routed_scaling_factor
          f = Σ_e g_e (silu(u W1_e) ⊙ (u W3_e)) W2_e         no shared expert
      h += f
    logits = rms(h, embedding_norm) Eᵀ           (tied; ``lm_head`` where untied)

 - a SHARE of the expert layer (``num_routed_experts`` > ``num_experts``):
   the weights hold ``num_experts`` experts, those from
   ``expert_shard_index * num_experts`` on. The router scores all, the
   gates are normalised over all the chosen, and the sum runs over the
   held ones among them: a pair that chose an absent expert adds nothing.
 - every held expert runs on every token, weighted by its gate or by 0.

Weights are read in the layout the program stores them in, which is data,
not code: ``embedding`` [V, D], ``final_ln`` [D], ``lm_head`` [D, V] where
untied, and ``layers`` a stack a KIND of block — ``conv_dense`` /
``full_dense`` (the leading dense blocks), ``conv`` / ``full`` (the expert
blocks), each ``[blocks of the kind, ...]`` in layer order: ``ln1, ln2``
[n, D]; conv ``sc_in`` [n, D, 3 D], ``sc_conv`` [n, K, D], ``sc_out`` [n,
D, D]; full ``wq, wo`` [n, D, D], ``wk, wv`` [n, D, Hkv Dh], ``q_norm,
k_norm`` [n, Dh]; dense ``w_gate, w_up`` [n, D, F], ``w_down`` [n, F, D];
experts ``router`` [n, D, E], ``router_bias`` [n, E], ``e_gate, e_up`` [n,
held, D, Fe], ``e_down`` [n, held, Fe, D].

``WRONG``: names of WRONG models, for ``check_limits_lfm2.py`` and the
parity tests' cases that a tolerance has to refuse.
"""

from __future__ import annotations

from typing import Any, Dict, FrozenSet, Optional

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512
HEAD_BLOCK = 4096
GATE_EPS = 1e-6  # HF's, added to the chosen scores' sum
WRONG = (
    "silu_after_conv",  # the Mamba habit
    "no_b_gate",
    "no_c_gate",
    "taps_reversed",
    "bias_left_out_of_choice",
    "bias_added_to_gates",
    "gates_not_renormalised",
    "softmax_for_sigmoid",
    "no_qk_norm",
    "conv_products_in_bfloat16",  # each w_j ⊙ z rounded before the sum
    "matmuls_in_float8",  # the nearest precision below bfloat16
)
NONE: FrozenSet[str] = frozenset()
_KIND = {"conv": "conv", "full_attention": "full"}


def f32(a):
    return jnp.asarray(a, jnp.float32)


def mm(a, b, wrong: FrozenSet[str] = NONE):
    a, b = f32(a), f32(b)
    if "matmuls_in_float8" in wrong:
        a, b = (t.astype(jnp.float8_e4m3fn).astype(jnp.float32)
                for t in (a, b))
    return jnp.matmul(a, b, precision=HI)


def eps_of(cfg: Dict[str, Any]) -> float:
    return cfg.get("norm_eps", 1e-5)


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * f32(w)


# ---------------- the two mixers ----------------

def taps(z, w, wrong: FrozenSet[str] = NONE):
    """[T, C] -> [T, C]: ``c_t = Σ_j w_j ⊙ z_{t-(K-1)+j}``, depthwise,
    causal, no bias: K shifted products of ONE document — a tap before
    its first token reads 0."""
    K, T = w.shape[0], z.shape[0]
    w = f32(w)[::-1] if "taps_reversed" in wrong else f32(w)
    padded = jnp.concatenate([jnp.zeros((K - 1, z.shape[1])), z], 0)
    products = [w[j] * padded[j:j + T] for j in range(K)]
    if "conv_products_in_bfloat16" in wrong:
        products = [f32(jax.lax.reduce_precision(p, 8, 7)) for p in products]
    return sum(products)


def shortconv(u, cfg: Dict[str, Any], lp, wrong: FrozenSet[str] = NONE):
    """The doubly gated short convolution on ``u`` [T, D], one document."""
    Bg, Cg, x = jnp.split(mm(u, lp["sc_in"], wrong), 3, axis=-1)
    c = taps(x if "no_b_gate" in wrong else Bg * x, lp["sc_conv"], wrong)
    if "silu_after_conv" in wrong:
        c = jax.nn.silu(c)
    return mm(c if "no_c_gate" in wrong else Cg * c, lp["sc_out"], wrong)


def head_dim(cfg: Dict[str, Any]) -> int:
    return cfg.get("head_dim") or (cfg["hidden_size"]
                                   // cfg["num_attention_heads"])


def rope(x, theta: float):
    """x [T, H, Dh]: rotate-half over the whole head, positions 0..T-1."""
    T, _, dh = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None]
    half = dh // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def attention(u, cfg: Dict[str, Any], lp, wrong: FrozenSet[str] = NONE):
    """GQA on ``u`` [T, D], one document: q and k normed a head before
    RoPE, a masked softmax a block of queries at a time."""
    nq, nkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   head_dim(cfg))
    T, eps = u.shape[0], eps_of(cfg)
    theta = float(cfg["rope_parameters"]["rope_theta"])
    q = mm(u, lp["wq"], wrong).reshape(T, nq, dh)
    k = mm(u, lp["wk"], wrong).reshape(T, nkv, dh)
    v = mm(u, lp["wv"], wrong).reshape(T, nkv, dh)
    if "no_qk_norm" not in wrong:
        q, k = rms(q, lp["q_norm"], eps), rms(k, lp["k_norm"], eps)
    q = rope(q, theta).reshape(T, nkv, nq // nkv, dh)
    k = rope(k, theta)
    pk = jnp.arange(T)[None, :]
    out = []
    for t0 in range(0, T, QUERY_BLOCK):
        pq = jnp.arange(t0, min(t0 + QUERY_BLOCK, T))[:, None]
        s = jnp.einsum("tkgd,skd->kgts", q[t0:t0 + QUERY_BLOCK], k,
                       precision=HI) * dh ** -0.5
        p = jax.nn.softmax(jnp.where((pk <= pq)[None, None], s, -jnp.inf), -1)
        out.append(jnp.einsum("kgts,skd->tkgd", p, v, precision=HI))
    return mm(jnp.concatenate(out, 0).reshape(T, nq * dh), lp["wo"], wrong)


# ---------------- the two FFNs ----------------

def swiglu(x, w_gate, w_up, w_down, wrong: FrozenSet[str] = NONE):
    return mm(jax.nn.silu(mm(x, w_gate, wrong)) * mm(x, w_up, wrong),
              w_down, wrong)


def chosen(x, cfg: Dict[str, Any], lp, wrong: FrozenSet[str] = NONE):
    """([T, E] scores, [T, k] indices of the chosen experts)."""
    logits = mm(x, lp["router"])
    scores = (jax.nn.softmax(logits, -1) if "softmax_for_sigmoid" in wrong
              else jax.nn.sigmoid(logits))
    by = scores if "bias_left_out_of_choice" in wrong else (
        scores + f32(lp["router_bias"]))
    return scores, jnp.argsort(-by, axis=-1)[:, :cfg["num_experts_per_tok"]]


def gates(x, cfg: Dict[str, Any], lp, wrong: FrozenSet[str] = NONE):
    """[T, D] -> the dense [T, routed] gate matrix: each chosen expert's
    score over the chosen ones' sum, times ``routed_scaling_factor``; 0
    elsewhere."""
    scores, idx = chosen(x, cfg, lp, wrong)
    if "bias_added_to_gates" in wrong:
        scores = scores + f32(lp["router_bias"])
    top = jnp.take_along_axis(scores, idx, -1)
    if cfg.get("norm_topk_prob", True) and (
            "gates_not_renormalised" not in wrong):
        top = top / (jnp.sum(top, -1, keepdims=True) + GATE_EPS)
    top = top * float(cfg.get("routed_scaling_factor", 1.0))
    return jnp.zeros_like(scores).at[
        jnp.arange(x.shape[0])[:, None], idx].set(top)


def first_held(cfg: Dict[str, Any]) -> int:
    return int(cfg.get("expert_shard_index", 0) or 0) * cfg["num_experts"]


def moe(x, cfg: Dict[str, Any], lp, wrong: FrozenSet[str] = NONE):
    """One expert layer on ``x`` [T, D] — on a share, its part of it:
    every held expert on every token, times its gate (0 where the token
    did not choose it)."""
    g = gates(x, cfg, lp, wrong)
    first = first_held(cfg)
    out = jnp.zeros_like(x)
    for e in range(cfg["num_experts"]):
        out = out + g[:, first + e, None] * swiglu(
            x, lp["e_gate"][e], lp["e_up"][e], lp["e_down"][e], wrong)
    return out


# ---------------- the model ----------------

def layers_of(params: Dict[str, Any], cfg: Dict[str, Any]):
    """[(mixer kind, is dense, that layer's parameters)] in layer order."""
    seen: Dict[str, int] = {}
    dense = int(cfg.get("num_dense_layers") or 0)
    out = []
    for layer, t in enumerate(
            cfg["layer_types"][:cfg["num_hidden_layers"]]):
        kind = _KIND[t] + ("_dense" if layer < dense else "")
        i = seen.get(kind, 0)
        seen[kind] = i + 1
        out.append((_KIND[t], layer < dense,
                    {k: w[i] for k, w in params["layers"][kind].items()}))
    return out


def block(h, kind: str, dense: bool, cfg: Dict[str, Any], lp,
          wrong: FrozenSet[str] = NONE):
    eps = eps_of(cfg)
    mixer = shortconv if kind == "conv" else attention
    h = h + mixer(rms(h, lp["ln1"], eps), cfg, lp, wrong)
    u = rms(h, lp["ln2"], eps)
    if dense:
        return h + swiglu(u, lp["w_gate"], lp["w_up"], lp["w_down"], wrong)
    return h + moe(u, cfg, lp, wrong)


def hidden(params, cfg: Dict[str, Any], tokens,
           wrong: FrozenSet[str] = NONE):
    """[T, D]: the residual stream behind the last block."""
    h = f32(params["embedding"][tokens])
    for kind, dense, lp in layers_of(params, cfg):
        h = block(h, kind, dense, cfg, lp, wrong)
    return h


def head_of(params) -> jnp.ndarray:
    """[D, V]: ``lm_head``, or the embedding's transpose where tied."""
    return params["lm_head"] if "lm_head" in params else f32(
        params["embedding"]).T


def logits(params, cfg: Dict[str, Any], tokens,
           wrong: FrozenSet[str] = NONE) -> jnp.ndarray:
    """[T, V] float32 logits of ONE document ``tokens`` [T]. ``cfg`` holds
    the HF config keys of the configuration file."""
    h = hidden(params, cfg, jnp.asarray(tokens, jnp.int32), wrong)
    return mm(rms(h, params["final_ln"], eps_of(cfg)), head_of(params), wrong)


def token_logprobs(params, cfg, tokens,
                   wrong: FrozenSet[str] = NONE) -> jnp.ndarray:
    """[T-1]: log p(tokens[t+1] | tokens[:t+1]) for t = 0..T-2 — what the
    PPO actor's inference pass returns for a document; the head a block
    of tokens at a time."""
    tokens = jnp.asarray(tokens, jnp.int32)
    h = rms(hidden(params, cfg, tokens, wrong), params["final_ln"],
            eps_of(cfg))[:-1]
    W = head_of(params)
    out = []
    for t0 in range(0, h.shape[0], HEAD_BLOCK):
        lp = jax.nn.log_softmax(mm(h[t0:t0 + HEAD_BLOCK], W, wrong), -1)
        out.append(jnp.take_along_axis(
            lp, tokens[1 + t0:1 + t0 + HEAD_BLOCK, None], -1)[:, 0])
    return jnp.concatenate(out)


def loss(params, cfg, tokens, weights: Optional[Any] = None) -> jnp.ndarray:
    """Negative logprob of one document, summed under ``weights`` [T-1]
    or (None) averaged: ``jax.grad`` of it is the gradient tests' oracle."""
    lp = token_logprobs(params, cfg, tokens)
    if weights is None:
        return -jnp.mean(lp)
    return -jnp.sum(lp * jnp.asarray(weights, jnp.float32))


def ppo_loss(params, cfg, tokens, old_logprobs, advantages, mask,
             eps_clip: float = 0.2) -> jnp.ndarray:
    """The clipped PPO surrogate of one document: ``-mean over the masked
    tokens of min(r A, clip(r, 1 ± eps_clip) A)`` with ``r = exp(logprob -
    old_logprob)``; ``old_logprobs``, ``advantages``, ``mask`` [T-1]."""
    ratio = jnp.exp(token_logprobs(params, cfg, tokens) - f32(old_logprobs))
    adv, mask = f32(advantages), f32(mask)
    surr = jnp.minimum(ratio * adv,
                       jnp.clip(ratio, 1 - eps_clip, 1 + eps_clip) * adv)
    return -jnp.sum(surr * mask) / jnp.maximum(jnp.sum(mask), 1.0)
