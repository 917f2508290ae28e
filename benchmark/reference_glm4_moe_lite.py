"""Plain reference: the forward pass, the PPO loss and (through
``jax.grad``) its gradients of a GLM-4.7-Flash model (zai-org,
``model_type`` ``glm4_moe_lite``) in straightforward float32 ``jax.numpy``
at ``precision="highest"`` — a Python loop over layers, over heads' query
blocks and over the held experts, a masked softmax of ONE document, the
rotary key rotated once and repeated, no kernel, no cache, no scan, no
sorting, no packing, and no import from ``areal_tpu``. Written from the
published ``config.json`` and HF's ``modeling_glm4_moe_lite.py`` /
``modeling_deepseek_v3.py`` AS RECALLED (each equation is listed under
``assumed`` in the configuration's file). ``h`` [T, D]::

    h = E[token]
    block l:
      u = rms(h, input_layernorm)                x / sqrt(mean x² + eps) · w
      c_q = rms(u W_qa, q_a_layernorm)           [T, q_lora_rank]
      q = c_q W_qb                               H heads of [q_nope | q_rope]
      [c_kv | k_r] = u W_kva                     kv_lora_rank + rope; the norm
      [k_nope | v] a head = rms(c_kv, kv_a_layernorm) W_kvb    spans c_kv ONLY
      q = [q_nope | rope(q_rope)]                rotate-half, theta, rope dims
      k = [k_nope | rope(k_r)]                   k_r ONE vector a token,
                                                 repeated for every head
      m = softmax(causal(q kᵀ / sqrt(nope + rope))) v W_o
      h += m
      u = rms(h, post_attention_layernorm)
      l <  first_k_dense_replace:  f = (silu(u Wg) ⊙ (u Wu)) Wd
      l >= first_k_dense_replace:  s = sigmoid_f32(u Wr)  over routed experts
          chosen = top_k of s + e_score_correction_bias  (it chooses only)
          g = s on the chosen;  g /= sum(g) + 1e-20  (norm_topk_prob)
          g *= routed_scaling_factor
          f = Σ_e g_e (silu(u Wg_e) ⊙ (u Wu_e)) Wd_e
              + (silu(u Wg_s) ⊙ (u Wu_s)) Wd_s   the shared expert: always on
                                                 no gate, NOT scaled
      h += f
    logits = rms(h, norm) W_head

 - a SHARE of the expert layer (``num_routed_experts`` >
   ``n_routed_experts``): the weights hold ``n_routed_experts`` experts,
   those from ``expert_shard_index * n_routed_experts`` on. The router
   scores all, the gates are normalised over all the chosen, and the sum
   runs over the held ones among them; the shared expert is whole.
 - every held expert runs on every token, weighted by its gate or by 0.

Weights are read in the layout the program stores them in, which is data,
not code: ``embedding`` [V, D], ``final_ln`` [D], ``lm_head`` [D, V], and
``layers`` a stack a KIND of block — ``full_dense`` (the leading dense
blocks), ``full`` (the expert blocks), or one stack where every block runs
the experts — each ``[blocks of the kind, ...]`` in layer order: ``ln1,
ln2`` [n, D]; ``wq_a`` [n, D, q_lora_rank], ``q_a_norm``, ``wq_b`` [n,
q_lora_rank, H (nope + rope)], ``wkv_a`` [n, D, kv_lora_rank + rope],
``kv_a_norm``, ``wkv_b`` [n, kv_lora_rank, H (nope + v)], ``wo`` [n, H v,
D]; dense ``w_gate, w_up`` [n, D, F], ``w_down`` [n, F, D]; experts
``router`` [n, D, E], ``router_bias`` [n, E], ``e_gate, e_up`` [n, held, D,
Fe], ``e_down`` [n, held, Fe, D], ``s_gate, s_up`` [n, D, Fs], ``s_down``.

``WRONG``: names of WRONG models, for ``check_limits_glm4_moe_lite.py`` and
the parity tests' cases that a tolerance has to refuse.
"""

from __future__ import annotations

from typing import Any, Dict, FrozenSet, Optional

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512
HEAD_BLOCK = 4096
GATE_EPS = 1e-20  # HF's, added to the chosen scores' sum
WRONG = (
    "no_rope_on_k_r",
    "rope_on_first_dims",  # the first rope dims of a head, not the last
    "kv_norm_over_all",  # kv_a_layernorm's statistic over c_kv AND k_r
    "no_q_latent_norm",
    "no_kv_latent_norm",
    "scale_by_nope_dim",  # qk_nope_head_dim ** -0.5
    "kv_b_split_v_first",  # a head of kv_b_proj read [v | k_nope]
    "k_r_per_head",  # every head its own (rolled) rotary key
    "bias_left_out_of_choice",
    "bias_added_to_gates",
    "gates_not_renormalised",
    "no_routed_scaling",
    "scaling_on_shared_too",
    "no_shared_expert",
    "softmax_for_sigmoid",
    "matmuls_in_float8",  # the nearest precision below bfloat16
)
NONE: FrozenSet[str] = frozenset()


def f32(a):
    return jnp.asarray(a, jnp.float32)


def mm(a, b, wrong: FrozenSet[str] = NONE):
    a, b = f32(a), f32(b)
    if "matmuls_in_float8" in wrong:
        a, b = (t.astype(jnp.float8_e4m3fn).astype(jnp.float32)
                for t in (a, b))
    return jnp.matmul(a, b, precision=HI)


def eps_of(cfg: Dict[str, Any]) -> float:
    return cfg.get("rms_norm_eps", 1e-5)


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * f32(w)


# ---------------- latent attention ----------------

def rope(x, theta: float):
    """x [T, H, Dr]: rotate-half over all of ``Dr``, positions 0..T-1."""
    T, _, dr = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None]
    half = dr // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def qkv(u, cfg: Dict[str, Any], lp, wrong: FrozenSet[str] = NONE):
    """(q, k, v), each [T, H, .]: the latent projection path on ``u``
    [T, D], one document."""
    H, eps = cfg["num_attention_heads"], eps_of(cfg)
    nope, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                    cfg["v_head_dim"])
    r, T = cfg["kv_lora_rank"], u.shape[0]
    theta = float(cfg["rope_theta"])
    c_q = mm(u, lp["wq_a"], wrong)
    if "no_q_latent_norm" not in wrong:
        c_q = rms(c_q, lp["q_a_norm"], eps)
    q = mm(c_q, lp["wq_b"], wrong).reshape(T, H, nope + dr)
    ckv = mm(u, lp["wkv_a"], wrong)
    c_kv, k_r = ckv[:, :r], ckv[:, r:]
    if "kv_norm_over_all" in wrong:
        scale = jax.lax.rsqrt(jnp.mean(ckv * ckv, -1, keepdims=True) + eps)
        c_kv, k_r = c_kv * scale * f32(lp["kv_a_norm"]), k_r * scale
    elif "no_kv_latent_norm" not in wrong:
        c_kv = rms(c_kv, lp["kv_a_norm"], eps)
    kv = mm(c_kv, lp["wkv_b"], wrong).reshape(T, H, nope + dv)
    if "kv_b_split_v_first" in wrong:
        v, k_nope = kv[..., :dv], kv[..., dv:]
    else:
        k_nope, v = kv[..., :nope], kv[..., nope:]
    k_r = jnp.repeat(k_r[:, None, :], H, axis=1)  # one a token, every head's
    if "k_r_per_head" in wrong:
        k_r = jnp.stack([jnp.roll(k_r[:, h], h, axis=-1) for h in range(H)], 1)
    q_nope, q_r = q[..., :nope], q[..., nope:]
    if "rope_on_first_dims" in wrong:
        q = jnp.concatenate([q_nope, q_r], -1)
        k = jnp.concatenate([k_nope, k_r], -1)
        q = jnp.concatenate([rope(q[..., :dr], theta), q[..., dr:]], -1)
        k = jnp.concatenate([rope(k[..., :dr], theta), k[..., dr:]], -1)
        return q, k, v
    if "no_rope_on_k_r" not in wrong:
        k_r = rope(k_r, theta)
    return (jnp.concatenate([q_nope, rope(q_r, theta)], -1),
            jnp.concatenate([k_nope, k_r], -1), v)


def attention(u, cfg: Dict[str, Any], lp, wrong: FrozenSet[str] = NONE):
    """The attention branch on ``u`` [T, D], one document: a masked
    softmax a block of queries at a time."""
    H, T = cfg["num_attention_heads"], u.shape[0]
    q, k, v = qkv(u, cfg, lp, wrong)
    width = (cfg["qk_nope_head_dim"] if "scale_by_nope_dim" in wrong
             else q.shape[-1])
    pk = jnp.arange(T)[None, :]
    out = []
    for t0 in range(0, T, QUERY_BLOCK):
        pq = jnp.arange(t0, min(t0 + QUERY_BLOCK, T))[:, None]
        s = jnp.einsum("thd,shd->hts", q[t0:t0 + QUERY_BLOCK], k,
                       precision=HI) * width ** -0.5
        p = jax.nn.softmax(jnp.where((pk <= pq)[None], s, -jnp.inf), -1)
        out.append(jnp.einsum("hts,shd->thd", p, v, precision=HI))
    return mm(jnp.concatenate(out, 0).reshape(T, H * v.shape[-1]), lp["wo"],
              wrong)


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
    if "no_routed_scaling" not in wrong:
        top = top * float(cfg.get("routed_scaling_factor", 1.0))
    return jnp.zeros_like(scores).at[
        jnp.arange(x.shape[0])[:, None], idx].set(top)


def first_held(cfg: Dict[str, Any]) -> int:
    return int(cfg.get("expert_shard_index", 0) or 0) * cfg["n_routed_experts"]


def moe(x, cfg: Dict[str, Any], lp, wrong: FrozenSet[str] = NONE):
    """One expert layer on ``x`` [T, D] — on a share, its part of it:
    every held expert on every token, times its gate (0 where the token
    did not choose it), plus the shared expert, whole."""
    g = gates(x, cfg, lp, wrong)
    first = first_held(cfg)
    out = jnp.zeros_like(x)
    for e in range(cfg["n_routed_experts"]):
        out = out + g[:, first + e, None] * swiglu(
            x, lp["e_gate"][e], lp["e_up"][e], lp["e_down"][e], wrong)
    if "s_up" not in lp or "no_shared_expert" in wrong:
        return out
    shared = swiglu(x, lp["s_gate"], lp["s_up"], lp["s_down"], wrong)
    if "scaling_on_shared_too" in wrong:
        shared = shared * float(cfg.get("routed_scaling_factor", 1.0))
    return out + shared


# ---------------- the model ----------------

def layers_of(params: Dict[str, Any], cfg: Dict[str, Any]):
    """[(is dense, that layer's parameters)] in layer order."""
    n = cfg["num_hidden_layers"]
    dense = min(int(cfg.get("first_k_dense_replace") or 0), n)
    seen: Dict[str, int] = {}
    out = []
    for layer in range(n):
        kind = "full_dense" if layer < dense else "full"
        tree = params["layers"][kind] if dense else params["layers"]
        i = seen.get(kind, 0)
        seen[kind] = i + 1
        out.append((layer < dense, {k: w[i] for k, w in tree.items()}))
    return out


def block(h, dense: bool, cfg: Dict[str, Any], lp,
          wrong: FrozenSet[str] = NONE):
    eps = eps_of(cfg)
    h = h + attention(rms(h, lp["ln1"], eps), cfg, lp, wrong)
    u = rms(h, lp["ln2"], eps)
    if dense:
        return h + swiglu(u, lp["w_gate"], lp["w_up"], lp["w_down"], wrong)
    return h + moe(u, cfg, lp, wrong)


def hidden(params, cfg: Dict[str, Any], tokens,
           wrong: FrozenSet[str] = NONE):
    """[T, D]: the residual stream behind the last block."""
    h = f32(params["embedding"][tokens])
    for dense, lp in layers_of(params, cfg):
        h = block(h, dense, cfg, lp, wrong)
    return h


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
