"""Plain reference: the forward pass of Mellum 2 (JetBrains
``Mellum2-12B-A2.5B-Instruct``) in straightforward float32 ``jax.numpy`` at
``precision="highest"`` — no kernel, no cache, no scan, no sorting, and no
import from ``areal_tpu``. Written from the published ``config.json``
alone. Per layer l of kind t(l) in {sliding, full} (``layer_types``),
``h`` [T, D]::

    x = rms(h, ln1)
    q = x Wq    k = x Wk    v = x Wv     (no biases, no q/k norm)
    q, k = rope_t(q), rope_t(k)          rotate-half; the table of kind t
    h += softmax(mask_t(q k^T / sqrt(Dh))) v Wo      32 q heads on 4 kv heads
    x = rms(h, ln2)
    p = softmax_f32(x Wr) over the 64 experts      (top_p, top_i) = top_8(p)
    g = top_p / sum(top_p)                         (norm_topk_prob true)
    h += sum_j g_j * Wdown[e_j]( silu(Wgate[e_j] x) * Wup[e_j] x )

then the final norm and the untied head.

 - mask: causal; on a sliding layer a query at position p sees the keys at
   p - window + 1 .. p (``0 <= p_q - p_k < window``), on a full layer
   everything before it. Attention runs a block of queries at a time so
   that 4096 tokens fit.
 - rope: sliding layers ``inv_freq_i = theta^(-2i/Dh)``; full layers YaRN
   as ``transformers`` computes it (``_compute_yarn_parameters``):
   ``pos_i = theta^(2i/Dh)``, ``dim(n) = Dh ln(orig / (2 pi n)) / (2 ln
   theta)``, ``low = max(floor(dim(beta_fast)), 0)``, ``high =
   min(ceil(dim(beta_slow)), Dh - 1)``, ``ramp_i = clip((i - low) / (high
   - low), 0, 1)``, ``inv_freq_i = (1 - ramp_i) / pos_i + ramp_i / (factor
   pos_i)``, and cos and sin both times the attention factor.
 - a SHARE of the expert layer (``num_routed_experts`` > ``num_experts``):
   the weights hold ``num_experts`` experts, those from
   ``expert_shard_index * num_experts`` on. The router scores all, the
   gates are normalised over all 8 chosen, and the sum runs over the held
   ones among them: a pair that chose an absent expert adds nothing.
 - every held expert runs on every token, weighted by its gate or by 0: a
   plain loop, so that no chosen pair can be lost to a sort.
 - departure: the "MTP head" the model card describes has no key in
   ``config.json`` and is left out.

Weights are read in the layout the program stores them in, which is data,
not code: ``embedding`` [V, D], ``layers/{ln1,ln2}`` [n, D],
``layers/{wq,wk,wv,wo}`` [n, in, out], ``layers/router`` [n, D, 64],
``layers/{e_gate,e_up}`` [n, E, D, F], ``layers/e_down`` [n, E, F, D],
``final_ln`` [D], ``lm_head`` [D, V].
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def inv_freq(rope: Dict[str, Any], dh: int) -> jnp.ndarray:
    """[Dh/2] inverse frequencies of one ``rope_parameters`` block."""
    theta = float(rope["rope_theta"])
    pos = theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    if rope.get("rope_type", "default") == "default":
        return 1.0 / pos
    assert rope["rope_type"] == "yarn", rope["rope_type"]
    orig = rope["original_max_position_embeddings"]

    def dim(n):
        return dh * math.log(orig / (2 * math.pi * n)) / (2 * math.log(theta))

    low = max(math.floor(dim(rope["beta_fast"])), 0)
    high = min(math.ceil(dim(rope["beta_slow"])), dh - 1)
    ramp = jnp.clip((jnp.arange(dh // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    return (1 - ramp) / pos + ramp / (rope["factor"] * pos)


def attention_factor(rope: Dict[str, Any]) -> float:
    if rope.get("rope_type", "default") == "default":
        return 1.0
    if rope.get("attention_factor") is not None:
        return float(rope["attention_factor"])
    return 0.1 * math.log(rope["factor"]) + 1.0


def _rope(x, rope: Dict[str, Any]):
    """x: [T, H, Dh]; rotate-half convention, positions 0..T-1."""
    T, _, dh = x.shape
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq(rope, dh)[None]
    scale = attention_factor(rope)
    cos = (jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1) * scale)[:, None]
    sin = (jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1) * scale)[:, None]
    half = dh // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def attention(q, k, v, window=None):
    """q [T, Hq, Dh], k / v [T, Hkv, Dh] → [T, Hq, Dh]: causal softmax
    attention, each group of Hq/Hkv query heads on its key/value head,
    keys further back than ``window`` - 1 masked; a block of queries at a
    time against all keys."""
    T, nq, dh = q.shape
    nkv = k.shape[1]
    qg = q.reshape(T, nkv, nq // nkv, dh)
    pk = jnp.arange(T)[None, :]
    out = []
    for t0 in range(0, T, QUERY_BLOCK):
        pq = jnp.arange(t0, min(t0 + QUERY_BLOCK, T))[:, None]
        see = pk <= pq
        if window is not None:
            see = see & (pq - pk < window)
        s = jnp.einsum("tkgd,skd->kgts", qg[t0:t0 + QUERY_BLOCK], k,
                       precision=HI) / jnp.sqrt(jnp.float32(dh))
        p = jax.nn.softmax(jnp.where(see[None, None], s, -jnp.inf), -1)
        out.append(jnp.einsum("kgts,skd->tkgd", p, v, precision=HI))
    return jnp.concatenate(out, 0).reshape(T, nq, dh)


def gates(probs, top_k: int, norm_topk_prob: bool):
    """[T, E] router probabilities → the dense [T, E] gate matrix: the
    probability of each of the ``top_k`` largest (over their sum when
    ``norm_topk_prob``), 0 elsewhere."""
    idx = jnp.argsort(-probs, axis=-1)[:, :top_k]  # [T, k]
    chosen = jnp.sum(jax.nn.one_hot(idx, probs.shape[-1], dtype=probs.dtype),
                     axis=1)  # [T, E] 0/1
    g = probs * chosen
    if norm_topk_prob:
        g = g / jnp.sum(g, -1, keepdims=True)
    return g


def experts(x, g, w_gate, w_up, w_down):
    """[T, D] → [T, D]: every expert whose weights are given on every
    token, weighted by its column of ``g`` [T, held]."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    mm = lambda a, b: jnp.matmul(a, b, precision=HI)  # noqa: E731
    y = jnp.zeros_like(x)
    for e in range(g.shape[-1]):
        h = jax.nn.silu(mm(x, f32(w_gate[e]))) * mm(x, f32(w_up[e]))
        y = y + g[:, e:e + 1] * mm(h, f32(w_down[e]))
    return y


def held_experts(cfg: Dict[str, Any]):
    """(index of the first expert held, experts held): all of them, or
    the share the configuration names."""
    held = cfg["num_experts"]
    return held * int(cfg.get("expert_shard_index") or 0), held


def moe(x, cfg: Dict[str, Any], router, w_gate, w_up, w_down):
    """One expert layer on ``x`` [T, D] — on a share, its part of it."""
    probs = jax.nn.softmax(jnp.matmul(x, jnp.asarray(router, jnp.float32),
                                      precision=HI), -1)
    g = gates(probs, cfg["num_experts_per_tok"], cfg["norm_topk_prob"])
    first, held = held_experts(cfg)
    return experts(x, g[:, first:first + held], w_gate, w_up, w_down)


def logits(params: Dict[str, Any], cfg: Dict[str, Any], tokens) -> jnp.ndarray:
    """[T, V] float32 logits of ONE sequence ``tokens`` [T]. ``cfg`` holds
    the HF config keys of the configuration file."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    mm = lambda a, b: jnp.matmul(a, b, precision=HI)  # noqa: E731
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    T = tokens.shape[0]
    L = params["layers"]
    h = f32(params["embedding"][tokens])
    for i, kind in enumerate(cfg["layer_types"][:cfg["num_hidden_layers"]]):
        rope = cfg["rope_parameters"][kind]
        window = cfg["sliding_window"] if kind == "sliding_attention" else None
        x = _rms(h, f32(L["ln1"][i]), eps)
        q = _rope(mm(x, f32(L["wq"][i])).reshape(T, nq, dh), rope)
        k = _rope(mm(x, f32(L["wk"][i])).reshape(T, nkv, dh), rope)
        v = mm(x, f32(L["wv"][i])).reshape(T, nkv, dh)
        a = attention(q, k, v, window).reshape(T, nq * dh)
        h = h + mm(a, f32(L["wo"][i]))
        x = _rms(h, f32(L["ln2"][i]), eps)
        h = h + moe(x, cfg, L["router"][i], L["e_gate"][i], L["e_up"][i],
                    L["e_down"][i])
    h = _rms(h, f32(params["final_ln"]), eps)
    return mm(h, f32(params["lm_head"]))


def token_logprobs(params, cfg, tokens) -> jnp.ndarray:
    """[T-1]: log p(tokens[t+1] | tokens[:t+1]) for t = 0..T-2."""
    tokens = jnp.asarray(tokens, jnp.int32)
    lp = jax.nn.log_softmax(logits(params, cfg, tokens)[:-1], -1)
    return jnp.take_along_axis(lp, tokens[1:, None], -1)[:, 0]
