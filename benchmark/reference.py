"""Plain reference: the forward pass of a dense GQA transformer of the
Qwen2 family (RMSNorm, rotate-half RoPE, biased q/k/v projections, SwiGLU,
tied or untied output head) in straightforward float32 ``jax.numpy`` at
``precision="highest"`` — no kernel, no cache, no batching, and no import
from ``areal_tpu``. It follows the published architecture (Qwen2 technical
report; the HF ``Qwen2ForCausalLM`` it ships as); it reads weights in the
layout the program stores them in, which is data, not code: ``embedding``
[V, D], ``layers/{ln1,ln2}`` [n, D], ``layers/{wq,wk,wv,wo,w_gate,w_up,
w_down}`` [n, in, out], ``layers/{bq,bk,bv}`` [n, out], ``final_ln`` [D]
and ``lm_head`` [D, V] when the head is untied.
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


def logits(params: Dict[str, Any], cfg: Dict[str, Any], tokens) -> jnp.ndarray:
    """[T, V] float32 logits of ONE sequence ``tokens`` [T]. ``cfg`` holds
    the HF config keys of the configuration file."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    mm = lambda a, b: jnp.matmul(a, b, precision=HI)  # noqa: E731
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg.get("head_dim") or cfg["hidden_size"] // nq
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    T = tokens.shape[0]
    L = params["layers"]
    h = f32(params["embedding"][tokens])
    causal = jnp.tril(jnp.ones((T, T), bool))
    for i in range(cfg["num_hidden_layers"]):
        x = _rms(h, f32(L["ln1"][i]), eps)
        q = (mm(x, f32(L["wq"][i])) + f32(L["bq"][i])).reshape(T, nq, dh)
        k = (mm(x, f32(L["wk"][i])) + f32(L["bk"][i])).reshape(T, nkv, dh)
        v = (mm(x, f32(L["wv"][i])) + f32(L["bv"][i])).reshape(T, nkv, dh)
        q, k = _rope(q, theta), _rope(k, theta)
        # grouped-query: q head j reads kv head j // (nq // nkv)
        k = jnp.repeat(k, nq // nkv, axis=1)
        v = jnp.repeat(v, nq // nkv, axis=1)
        s = jnp.einsum("thd,shd->hts", q, k, precision=HI) / jnp.sqrt(
            jnp.float32(dh))
        s = jnp.where(causal[None], s, -jnp.inf)
        a = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v,
                       precision=HI).reshape(T, nq * dh)
        h = h + mm(a, f32(L["wo"][i]))
        x = _rms(h, f32(L["ln2"][i]), eps)
        h = h + mm(jax.nn.silu(mm(x, f32(L["w_gate"][i])))
                   * mm(x, f32(L["w_up"][i])), f32(L["w_down"][i]))
    h = _rms(h, f32(params["final_ln"]), eps)
    head = (f32(params["embedding"]).T if cfg["tie_word_embeddings"]
            else f32(params["lm_head"]))
    return mm(h, head)


def token_logprobs(params, cfg, tokens) -> jnp.ndarray:
    """[T-1]: log p(tokens[t+1] | tokens[:t+1]) for t = 0..T-2."""
    tokens = jnp.asarray(tokens, jnp.int32)
    lp = jax.nn.log_softmax(logits(params, cfg, tokens)[:-1], -1)
    return jnp.take_along_axis(lp, tokens[1:, None], -1)[:, 0]
