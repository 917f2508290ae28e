"""Plain reference: the forward pass of Arcee Trinity-Mini (``model_type``
``afmoe``) in straightforward float32 ``jax.numpy`` at
``precision="highest"`` — a Python loop over layers, no kernel, no cache,
no scan, no sorting, no packing, and no import from ``areal_tpu``. Written
from the published ``config.json`` and, where no key of it says (marked
†, each listed under ``assumed`` in the configuration's file), from the
family's public modelling code. ``h`` [T, D]::

    h = E[token] * sqrt(hidden_size)                    (mup_enabled; † the factor)
    block l of kind S (sliding_attention) or F (full_attention):
      x = rms(h, ln1)
      q = x Wq   k = x Wk   v = x Wv   g = x Wg                         († Wg)
      q = rms_head(q, q_norm)   k = rms_head(k, k_norm)     over each head's 128 (†)
      S: q, k = rope(q), rope(k)   rotate-half, theta, all of the head
      F: no position embedding                                          (†)
      a = softmax(mask(q k^T / sqrt(Dh))) v       32 q heads on 4 kv heads
      a = a * sigmoid(g)                                                (†)
      h += rms(a Wo, ln1_post)                                          (†)
      x = rms(h, ln2)
      l <  num_dense_layers:  m = (silu(x W1) * (x W3)) W2
      l >= num_dense_layers:  s = sigmoid_f32(x Wr)  over the routed experts
          chosen = top_k of s + expert_bias          (the bias chooses only)
          w = s on the chosen;  w /= sum(w) + 1e-20  (route_norm);  w *= route_scale
          m = sum_e w_e FFN_e(x) + FFN_shared(x)     each a SwiGLU
      h += rms(m, ln2_post)                                             (†)
    logits = rms(h, final_ln) W_head                    (untied)

 - mask: causal; on a sliding layer a query at position p sees the keys at
   p - window + 1 .. p (``0 <= p_q - p_k < window``). Attention runs a
   block of queries at a time, and the head a block of tokens at a time,
   so that 8192 tokens fit the chip beside a trainer's state.
 - a SHARE of the expert layer (``num_routed_experts`` > ``num_experts``):
   the weights hold ``num_experts`` experts, those from
   ``expert_shard_index * num_experts`` on. The router scores all, the
   gates are normalised over all the chosen, and the sum runs over the
   held ones among them: a pair that chose an absent expert adds nothing.
 - every held expert runs on every token, weighted by its gate or by 0: a
   plain loop, so that no chosen pair can be lost to a sort.

Weights are read in the layout the program stores them in, which is data,
not code: ``embedding`` [V, D], ``final_ln`` [D], ``lm_head`` [D, V], and
``layers`` a stack a KIND of block — ``sliding_dense`` / ``full_dense``
(the leading dense blocks), ``sliding`` / ``full`` (the expert blocks),
each ``[blocks of the kind, ...]`` in layer order — or one stack of all
blocks where ``num_dense_layers`` is 0: ``ln1, ln1_post, ln2, ln2_post``
[n, D], ``wq, wg`` [n, D, Hq Dh], ``wk, wv`` [n, D, Hkv Dh], ``wo`` [n, Hq
Dh, D], ``q_norm, k_norm`` [n, Dh], dense ``w_gate, w_up`` [n, D, F],
``w_down`` [n, F, D]; experts ``router`` [n, D, E], ``router_bias`` [n,
E], ``e_gate, e_up`` [n, held, D, Fe], ``e_down`` [n, held, Fe, D],
``s_gate, s_up`` [n, D, Fs], ``s_down`` [n, Fs, D].

``wrong``: names of WRONG models, for ``check_limits_afmoe.py`` and the
parity tests' cases that a tolerance has to refuse (the others are wrong
CONFIGS: another ``sliding_window``, ``route_scale``, ``score_func``,
``num_shared_experts``): ``no_gate``, ``rope_on_full``, ``no_post_norms``,
``dense_as_experts`` (the leading dense blocks run the first expert
block's FFN), ``float8`` (both operands of the projections' and the
experts' products rounded to float8_e4m3, the nearest precision below
bfloat16).
"""

from __future__ import annotations

from typing import Any, Dict, FrozenSet

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512
HEAD_BLOCK = 2048
_KIND = {"sliding_attention": "sliding", "full_attention": "full"}


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _mm(a, b, float8: bool = False):
    a, b = _f32(a), _f32(b)
    if float8:
        a, b = (t.astype(jnp.float8_e4m3fn).astype(jnp.float32)
                for t in (a, b))
    return jnp.matmul(a, b, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f32(w)


def _rope(x, theta: float):
    """x: [T, H, Dh]; rotate-half convention, positions 0..T-1."""
    T, _, dh = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None]
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


def swiglu(x, w_gate, w_up, w_down, float8: bool = False):
    return _mm(jax.nn.silu(_mm(x, w_gate, float8)) * _mm(x, w_up, float8),
               w_down, float8)


def gates(x, cfg: Dict[str, Any], router, bias) -> jnp.ndarray:
    """[T, D] → the dense [T, routed] gate matrix: each chosen expert's
    score (over the chosen ones' sum where ``route_norm``) times
    ``route_scale``, 0 elsewhere."""
    logits = _mm(x, router)
    if cfg.get("score_func", "sigmoid") == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, -1)
    idx = jnp.argsort(-(scores + _f32(bias)), axis=-1)[
        :, :cfg["num_experts_per_tok"]]
    chosen = jnp.sum(jax.nn.one_hot(idx, scores.shape[-1],
                                    dtype=scores.dtype), axis=1)
    w = scores * chosen
    if cfg.get("route_norm", True):
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return w * float(cfg.get("route_scale", 1.0))


def moe(x, cfg: Dict[str, Any], lp, float8: bool = False):
    """One expert layer on ``x`` [T, D] — on a share, its part of it —
    beside the shared expert."""
    held = cfg["num_experts"]
    first = held * int(cfg.get("expert_shard_index") or 0)
    w = gates(x, cfg, lp["router"], lp["router_bias"])[:, first:first + held]
    y = jnp.zeros_like(x)
    for e in range(held):
        y = y + w[:, e:e + 1] * swiglu(
            x, lp["e_gate"][e], lp["e_up"][e], lp["e_down"][e], float8)
    if cfg.get("num_shared_experts"):
        y = y + swiglu(x, lp["s_gate"], lp["s_up"], lp["s_down"], float8)
    return y


def _layer_params(params, cfg):
    """[(HF layer type, is dense, that block's leaves)] in layer order."""
    L, seen, out = params["layers"], {}, []
    dense = int(cfg.get("num_dense_layers") or 0)
    types = cfg["layer_types"][:cfg["num_hidden_layers"]]
    for i, t in enumerate(types):
        if "ln1" in L:  # one stack of all blocks
            tree, j = L, i
        else:
            kind = _KIND[t] + ("_dense" if i < dense else "")
            tree, j = L[kind], seen.get(kind, 0)
            seen[kind] = j + 1
        out.append((t, i < dense, {k: v[j] for k, v in tree.items()}))
    return out


def hidden(params, cfg: Dict[str, Any], tokens,
           wrong: FrozenSet[str] = frozenset()) -> jnp.ndarray:
    """[T, D]: the residual stream behind the last block, of ONE sequence
    ``tokens`` [T]. ``cfg`` holds the HF config keys of the file."""
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    f8 = "float8" in wrong
    T = tokens.shape[0]
    h = _f32(params["embedding"][tokens])
    if cfg.get("mup_enabled"):
        h = h * jnp.sqrt(jnp.float32(cfg["hidden_size"]))
    layers = _layer_params(params, cfg)
    first_experts = next((lp for _, dense, lp in layers if not dense), None)

    def post(x, w):
        return x if "no_post_norms" in wrong else _rms(x, w, eps)

    for kind, dense, lp in layers:
        x = _rms(h, lp["ln1"], eps)
        q = _rms(_mm(x, lp["wq"], f8).reshape(T, nq, dh), lp["q_norm"], eps)
        k = _rms(_mm(x, lp["wk"], f8).reshape(T, nkv, dh), lp["k_norm"], eps)
        v = _mm(x, lp["wv"], f8).reshape(T, nkv, dh)
        if kind == "sliding_attention" or "rope_on_full" in wrong:
            q, k = (_rope(t, float(cfg["rope_theta"])) for t in (q, k))
        window = (cfg["sliding_window"] if kind == "sliding_attention"
                  else None)
        a = attention(q, k, v, window).reshape(T, nq * dh)
        if "no_gate" not in wrong:
            a = a * jax.nn.sigmoid(_mm(x, lp["wg"], f8))
        h = h + post(_mm(a, lp["wo"], f8), lp["ln1_post"])
        x = _rms(h, lp["ln2"], eps)
        if dense and "dense_as_experts" in wrong:
            m = moe(x, cfg, first_experts, f8)
        elif dense:
            m = swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"], f8)
        else:
            m = moe(x, cfg, lp, f8)
        h = h + post(m, lp["ln2_post"])
    return h


def logits(params, cfg, tokens, wrong: FrozenSet[str] = frozenset()):
    """[T, V] float32 logits of ONE sequence ``tokens`` [T]."""
    h = hidden(params, cfg, jnp.asarray(tokens, jnp.int32), wrong)
    return _mm(_rms(h, params["final_ln"], cfg["rms_norm_eps"]),
               params["lm_head"])


def token_logprobs(params, cfg, tokens,
                   wrong: FrozenSet[str] = frozenset()) -> jnp.ndarray:
    """[T-1]: log p(tokens[t+1] | tokens[:t+1]) for t = 0..T-2; the head
    a block of tokens at a time."""
    tokens = jnp.asarray(tokens, jnp.int32)
    h = _rms(hidden(params, cfg, tokens, wrong), params["final_ln"],
             cfg["rms_norm_eps"])[:-1]
    out = []
    for t0 in range(0, h.shape[0], HEAD_BLOCK):
        lp = jax.nn.log_softmax(
            _mm(h[t0:t0 + HEAD_BLOCK], params["lm_head"]), -1)
        out.append(jnp.take_along_axis(
            lp, tokens[1 + t0:1 + t0 + HEAD_BLOCK, None], -1)[:, 0])
    return jnp.concatenate(out)
