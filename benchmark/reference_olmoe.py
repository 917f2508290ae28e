"""Plain reference: the forward pass of OLMoE (``OlmoeForCausalLM``,
allenai/OLMoE-1B-7B) in straightforward float32 ``jax.numpy`` at
``precision="highest"`` — no kernel, no cache, no batching, no sorting, and
no import from ``areal_tpu``. Per layer, ``h`` [T, D]::

    x = rms(h, ln1)
    q = rms(x Wq, q_norm)   k = rms(x Wk, k_norm)   v = x Wv
        (no biases; the q/k norm spans ALL projected outputs, before the
         split into heads — HF modeling_olmoe.py: q_norm =
         OlmoeRMSNorm(hidden_size); written from memory, no network here)
    q, k = rope_rotate_half(q, k)       h += causal_softmax(q k^T / sqrt(Dh)) v Wo
    x = rms(h, ln2)
    p = softmax_f32(x Wr) over the E experts     (top_p, top_i) = top_k(p)
    gates = top_p as they are (norm_topk_prob false), renormalised if true
    h += sum_j gates_j * Wdown[e_j]( silu(Wgate[e_j] x) * Wup[e_j] x )

then the final norm and the untied head. Every expert runs on every
token, weighted by its gate or by 0: a plain loop, so that no chosen
(token, expert) pair can be lost to a capacity, a sort or an exchange.
Weights are read in the layout the program stores them in, which is data,
not code: ``embedding`` [V, D], ``layers/{ln1,ln2}`` [n, D],
``layers/{wq,wk,wv,wo}`` [n, in, out], ``layers/{q_norm,k_norm}`` [n, out],
``layers/router`` [n, D, E], ``layers/{e_gate,e_up}`` [n, E, D, F],
``layers/e_down`` [n, E, F, D], ``final_ln`` [D], ``lm_head`` [D, V]. They
may lie sharded over a mesh: ``jax.numpy`` runs on them where they are,
and one expert's matrices at a time are copied to float32.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: [T, H, Dh]; rotate-half convention."""
    T, _, dh = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    half = dh // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def gates(probs, top_k: int, norm_topk_prob: bool):
    """[T, E] router probabilities → the dense [T, E] gate matrix: the
    probability of each of the ``top_k`` largest, 0 elsewhere."""
    idx = jnp.argsort(-probs, axis=-1)[:, :top_k]  # [T, k]
    chosen = jnp.sum(jax.nn.one_hot(idx, probs.shape[-1], dtype=probs.dtype),
                     axis=1)  # [T, E] 0/1
    g = probs * chosen
    if norm_topk_prob:
        g = g / jnp.sum(g, -1, keepdims=True)
    return g


def experts(x, g, w_gate, w_up, w_down):
    """[T, D] → [T, D]: every expert on every token, weighted by its gate
    ``g`` [T, E] (0 where the token did not choose it)."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    mm = lambda a, b: jnp.matmul(a, b, precision=HI)  # noqa: E731
    y = jnp.zeros_like(x)
    for e in range(g.shape[-1]):
        h = jax.nn.silu(mm(x, f32(w_gate[e]))) * mm(x, f32(w_up[e]))
        y = y + g[:, e:e + 1] * mm(h, f32(w_down[e]))
    return y


def logits(params: Dict[str, Any], cfg: Dict[str, Any], tokens) -> jnp.ndarray:
    """[T, V] float32 logits of ONE sequence ``tokens`` [T]. ``cfg`` holds
    the HF config keys of the configuration file."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    mm = lambda a, b: jnp.matmul(a, b, precision=HI)  # noqa: E731
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg["hidden_size"] // nq
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    top_k, renorm = cfg["num_experts_per_tok"], cfg["norm_topk_prob"]
    T = tokens.shape[0]
    L = params["layers"]
    h = f32(params["embedding"][tokens])
    causal = jnp.tril(jnp.ones((T, T), bool))
    for i in range(cfg["num_hidden_layers"]):
        x = _rms(h, f32(L["ln1"][i]), eps)
        q = _rms(mm(x, f32(L["wq"][i])), f32(L["q_norm"][i]), eps)
        k = _rms(mm(x, f32(L["wk"][i])), f32(L["k_norm"][i]), eps)
        v = mm(x, f32(L["wv"][i])).reshape(T, nkv, dh)
        q = _rope(q.reshape(T, nq, dh), theta)
        k = _rope(k.reshape(T, nkv, dh), theta)
        k = jnp.repeat(k, nq // nkv, axis=1)
        v = jnp.repeat(v, nq // nkv, axis=1)
        s = jnp.einsum("thd,shd->hts", q, k, precision=HI) / jnp.sqrt(
            jnp.float32(dh))
        s = jnp.where(causal[None], s, -jnp.inf)
        a = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v,
                       precision=HI).reshape(T, nq * dh)
        h = h + mm(a, f32(L["wo"][i]))
        x = _rms(h, f32(L["ln2"][i]), eps)
        g = gates(jax.nn.softmax(mm(x, f32(L["router"][i])), -1), top_k,
                  renorm)
        h = h + experts(x, g, L["e_gate"][i], L["e_up"][i], L["e_down"][i])
    h = _rms(h, f32(params["final_ln"]), eps)
    head = (f32(params["embedding"]).T if cfg["tie_word_embeddings"]
            else f32(params["lm_head"]))
    return mm(h, head)


def token_logprobs(params, cfg, tokens) -> jnp.ndarray:
    """[T-1]: log p(tokens[t+1] | tokens[:t+1]) for t = 0..T-2."""
    tokens = jnp.asarray(tokens, jnp.int32)
    lp = jax.nn.log_softmax(logits(params, cfg, tokens)[:-1], -1)
    return jnp.take_along_axis(lp, tokens[1:, None], -1)[:, 0]
