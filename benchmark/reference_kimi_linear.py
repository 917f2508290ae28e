"""Plain reference: the forward pass, the PPO loss and (through
``jax.grad``) its gradients of a Kimi-Linear model (moonshotai,
``model_type`` ``kimi_linear``) in straightforward float32 ``jax.numpy`` at
``precision="highest"`` — a Python loop over layers and over the held
experts, the Kimi Delta Attention rule as a ``lax.scan`` over TOKENS (the
recurrence below, literally: no chunk, no inverse, no exponent of a
difference), latent attention as a masked softmax of ONE document with
nothing rotated, no kernel, no cache, no sorting, no packing, and no import
from ``areal_tpu``. Written from the published ``config.json`` and "Kimi
Linear: An Expressive, Efficient Attention Architecture" (arXiv 2510.26692)
AS RECALLED (each equation is listed under ``assumed`` in the
configuration's file). ``h`` [T, D]::

    h = E[token]
    block l (published number held_layers[l]):
      u = rms(h, input_layernorm)                x / sqrt(mean x² + eps) · w
      l in kda_layers — Kimi Delta Attention, H heads of dh:
        q = l2(silu(conv(u W_q))) · dh^-1/2      l2: x · rsqrt(Σ x² + 1e-6) a head
        k = l2(silu(conv(u W_k)));  v = silu(conv(u W_v))
                                                 conv: depthwise, causal, K taps, no bias
        β = sigmoid(u W_b)                       a head
        g = -exp(A_log) ⊙ softplus(u W_f↓ W_f↑ + dt_bias)
                                                 a key CHANNEL; A_log a head
        S ← Diag(e^{g_t}) S;  δ_t = β_t (v_t − Sᵀ k_t);  S ← S + k_t δ_tᵀ
        o_t = Sᵀ q_t                             S [dh, dh] a head, zero at t = 0
        m = (rms(o, o_norm) ⊙ sigmoid(u W_g↓ W_g↑)) W_o      the norm a head's dh
      l in full_attn_layers — latent attention, no position embedding:
        q = u W_q                                H heads of nope + rope, full rank
        [c_kv | k_s] = u W_kva                   kv_lora_rank + rope; the norm
        [k_nope | v] a head = rms(c_kv, kv_a_layernorm) W_kvb     spans c_kv ONLY
        k = [k_nope | k_s]                       k_s ONE vector a token, repeated
                                                 for every head, NOT rotated
        m = softmax(causal(q kᵀ / sqrt(nope + rope))) v W_o       v: v_head_dim wide
      h += m
      u = rms(h, post_attention_layernorm)
      number <= first_k_dense_replace:  f = (silu(u Wg) ⊙ (u Wu)) Wd
      else:  s = sigmoid_f32(u Wr)  over routed experts
          chosen = top_k of s + e_score_correction_bias  (it chooses only)
          g = s on the chosen;  g /= sum(g) + 1e-20  (moe_renormalize)
          g *= routed_scaling_factor
          f = Σ_e g_e (silu(u Wg_e) ⊙ (u Wu_e)) Wd_e
              + (silu(u Wg_s) ⊙ (u Wu_s)) Wd_s   the shared expert: always on,
                                                 no gate, NOT scaled
      h += f
    logits = rms(h, norm) W_head

 - a SHARE of the expert layer (``num_routed_experts`` > ``num_experts``):
   the weights hold ``num_experts`` experts, those from ``expert_shard_index
   * num_experts`` on. The router scores all, the gates are normalised over
   all the chosen, and the sum runs over the held ones among them; the
   shared expert is whole. Every held expert runs on every token, weighted
   by its gate or by 0.

Weights are read in the layout the program stores them in, which is data,
not code: ``embedding`` [V, D], ``final_ln`` [D], ``lm_head`` [D, V], and
``layers`` a stack a KIND of block — ``kda_dense``, ``kda``, ``full`` (and
``full_dense``) — each ``[blocks of the kind, ...]`` in layer order: ``ln1,
ln2`` [n, D]; KDA ``kda_qkv`` [n, D, q | k | v], ``kda_conv`` [n, K, q | k |
v] (tap K - 1 on the token itself), ``kda_gates_a`` [n, D, b (H) | f↓ | g↓],
``kda_f_b``, ``kda_g_b`` [n, rank, H dh], ``kda_A_log`` [n, H],
``kda_dt_bias`` [n, H dh], ``kda_norm`` [n, dh], ``kda_out`` [n, H dh, D];
attention ``wq`` [n, D, H (nope + rope)], ``wkv_a`` [n, D, kv_lora_rank +
rope], ``kv_a_norm``, ``wkv_b`` [n, kv_lora_rank, H (nope + v)], ``wo`` [n,
H v, D]; dense ``w_gate, w_up`` [n, D, F], ``w_down`` [n, F, D]; experts
``router`` [n, D, E], ``router_bias`` [n, E], ``e_gate, e_up`` [n, held, D,
Fe], ``e_down`` [n, held, Fe, D], ``s_gate, s_up`` [n, D, Fs], ``s_down``.

``WRONG``: names of WRONG models, for ``check_limits_kimi_linear.py`` and
the parity tests' cases that a tolerance has to refuse.
"""

from __future__ import annotations

from typing import Any, Dict, FrozenSet, List, Optional, Tuple

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512
HEAD_BLOCK = 4096
GATE_EPS = 1e-20  # added to the chosen scores' sum
L2_EPS = 1e-6  # inside the rsqrt of q's and k's l2 norm
STATE_CHUNK = 64  # "state_bf16_each_chunk" rounds the state this often
WRONG = (
    "decay_averaged_over_channels",  # one decay a head: a Gated DeltaNet rule
    "delta_before_decay",  # S ← S + k δᵀ with δ from the UNDECAYED state
    "silu_output_gate",  # Gated DeltaNet's gate in the sigmoid's place
    "no_l2_norm",
    "beta_left_out",
    "A_log_per_channel_read_as_zero",  # g = -softplus(.): exp(A_log) left out
    "no_dt_bias",
    "state_bf16_each_chunk",  # the carried state rounded every 64 tokens
    "conv_taps_reversed",
    "rope_on_latent_attention",  # rotate the last rope dims, as GLM does
    "kv_norm_over_all",  # kv_a_layernorm's statistic over c_kv AND k_s
    "scale_by_nope_dim",  # qk_nope_head_dim ** -0.5
    "kv_b_split_v_first",  # a head of kv_b_proj read [v | k_nope]
    "bias_left_out_of_choice",
    "gates_not_renormalised",
    "no_routed_scaling",  # the gates not times 2.446
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


# ---------------- Kimi Delta Attention ----------------

def conv(x, w, wrong: FrozenSet[str] = NONE):
    """x [T, C], w [K, C]: ``y_t = Σ_j w[K - 1 - j] x_{t-j}`` — depthwise,
    causal, zeros before the document."""
    K, T = w.shape[0], x.shape[0]
    w = f32(w)[::-1] if "conv_taps_reversed" in wrong else f32(w)
    xp = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x], 0)
    return sum(w[K - 1 - j] * xp[K - 1 - j:K - 1 - j + T] for j in range(K))


def l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def kda_inputs(u, cfg: Dict[str, Any], lp, wrong: FrozenSet[str] = NONE):
    """(q, k, v, g [T, H, dh], beta [T, H], the output gate's logits [T, H
    dh]) of one KDA mixer on ``u`` [T, D]."""
    lin = cfg["linear_attn_config"]
    H, dh, T = lin["num_heads"], lin["head_dim"], u.shape[0]
    rank = lp["kda_f_b"].shape[0]
    q, k, v = (a.reshape(T, H, dh) for a in jnp.split(jax.nn.silu(
        conv(mm(u, lp["kda_qkv"], wrong), lp["kda_conv"], wrong)), 3, -1))
    b, f, z = jnp.split(mm(u, lp["kda_gates_a"], wrong), [H, H + rank], -1)
    pre = mm(f, lp["kda_f_b"], wrong)
    if "no_dt_bias" not in wrong:
        pre = pre + f32(lp["kda_dt_bias"])
    g = -jax.nn.softplus(pre).reshape(T, H, dh)
    if "A_log_per_channel_read_as_zero" not in wrong:
        g = g * jnp.exp(f32(lp["kda_A_log"]))[None, :, None]
    if "decay_averaged_over_channels" in wrong:
        g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
    beta = (jnp.ones((T, H)) if "beta_left_out" in wrong
            else jax.nn.sigmoid(b))
    if "no_l2_norm" not in wrong:
        q, k = l2(q), l2(k)
    return q * dh ** -0.5, k, v, g, beta, mm(z, lp["kda_g_b"], wrong)


def delta_rule(q, k, v, g, beta, wrong: FrozenSet[str] = NONE):
    """The recurrence of the module's docstring, a token at a time: q, k,
    g [T, H, dk], v [T, H, dv], beta [T, H] -> o [T, H, dv]."""
    T, H, dk = q.shape
    dv = v.shape[-1]

    def step(S, x):  # S [H, dk, dv]
        q, k, v, g, b, t = x
        if "state_bf16_each_chunk" in wrong:
            # reduce_precision, not a cast there and back: XLA's TPU
            # compiler drops such a pair (excess precision is allowed)
            S = jnp.where(t % STATE_CHUNK == 0,
                          jax.lax.reduce_precision(S, 8, 7), S)
        old = S
        S = jnp.exp(g)[:, :, None] * S
        read = old if "delta_before_decay" in wrong else S
        delta = b[:, None] * (v - jnp.einsum("hkv,hk->hv", read, k,
                                             precision=HI))
        S = S + k[:, :, None] * delta[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q, precision=HI)

    _, o = jax.lax.scan(step, jnp.zeros((H, dk, dv), jnp.float32),
                        (q, k, v, g, beta, jnp.arange(T)))
    return o


def kda(u, cfg: Dict[str, Any], lp, wrong: FrozenSet[str] = NONE):
    """The KDA branch on ``u`` [T, D], one document."""
    T = u.shape[0]
    q, k, v, g, beta, z = kda_inputs(u, cfg, lp, wrong)
    o = rms(delta_rule(q, k, v, g, beta, wrong), lp["kda_norm"], eps_of(cfg))
    gate = jax.nn.silu(z) if "silu_output_gate" in wrong else (
        jax.nn.sigmoid(z))
    return mm(o.reshape(T, -1) * gate, lp["kda_out"], wrong)


# ---------------- latent attention ----------------

def rope(x, theta: float):
    """x [T, H, Dr]: rotate-half over all of ``Dr``, positions 0..T-1 (a
    WRONG model's: this family rotates nothing)."""
    T, _, dr = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None]
    half = dr // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def qkv(u, cfg: Dict[str, Any], lp, wrong: FrozenSet[str] = NONE):
    """(q, k [T, H, nope + rope], v [T, H, v_head_dim])."""
    H, eps = cfg["num_attention_heads"], eps_of(cfg)
    nope, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                    cfg["v_head_dim"])
    r, T = cfg["kv_lora_rank"], u.shape[0]
    q = mm(u, lp["wq"], wrong).reshape(T, H, nope + dr)
    ckv = mm(u, lp["wkv_a"], wrong)
    c_kv, k_s = ckv[:, :r], ckv[:, r:]
    if "kv_norm_over_all" in wrong:
        scale = jax.lax.rsqrt(jnp.mean(ckv * ckv, -1, keepdims=True) + eps)
        c_kv, k_s = c_kv * scale * f32(lp["kv_a_norm"]), k_s * scale
    else:
        c_kv = rms(c_kv, lp["kv_a_norm"], eps)
    kv = mm(c_kv, lp["wkv_b"], wrong).reshape(T, H, nope + dv)
    if "kv_b_split_v_first" in wrong:
        v, k_nope = kv[..., :dv], kv[..., dv:]
    else:
        k_nope, v = kv[..., :nope], kv[..., nope:]
    k_s = jnp.repeat(k_s[:, None, :], H, axis=1)  # one a token, every head's
    q_nope, q_s = q[..., :nope], q[..., nope:]
    if "rope_on_latent_attention" in wrong:
        theta = float(cfg.get("rope_theta", 10000.0))
        q_s, k_s = rope(q_s, theta), rope(k_s, theta)
    return (jnp.concatenate([q_nope, q_s], -1),
            jnp.concatenate([k_nope, k_s], -1), v)


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
    return scores, jnp.argsort(-by, axis=-1)[:, :cfg["num_experts_per_token"]]


def gates(x, cfg: Dict[str, Any], lp, wrong: FrozenSet[str] = NONE):
    """[T, D] -> the dense [T, routed] gate matrix: each chosen expert's
    score over the chosen ones' sum, times ``routed_scaling_factor``; 0
    elsewhere."""
    scores, idx = chosen(x, cfg, lp, wrong)
    top = jnp.take_along_axis(scores, idx, -1)
    if cfg.get("moe_renormalize", True) and (
            "gates_not_renormalised" not in wrong):
        top = top / (jnp.sum(top, -1, keepdims=True) + GATE_EPS)
    if "no_routed_scaling" not in wrong:
        top = top * float(cfg.get("routed_scaling_factor", 1.0))
    return jnp.zeros_like(scores).at[
        jnp.arange(x.shape[0])[:, None], idx].set(top)


def first_held(cfg: Dict[str, Any]) -> int:
    return int(cfg.get("expert_shard_index", 0) or 0) * cfg["num_experts"]


def routed(x, cfg: Dict[str, Any], lp, wrong: FrozenSet[str] = NONE):
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


def moe(x, cfg: Dict[str, Any], lp, wrong: FrozenSet[str] = NONE):
    """One expert layer on ``x`` [T, D] — on a share, its part of it —
    plus the shared expert, whole."""
    out = routed(x, cfg, lp, wrong)
    if "s_up" not in lp or "no_shared_expert" in wrong:
        return out
    shared = swiglu(x, lp["s_gate"], lp["s_up"], lp["s_down"], wrong)
    if "scaling_on_shared_too" in wrong:
        shared = shared * float(cfg.get("routed_scaling_factor", 1.0))
    return out + shared


# ---------------- the model ----------------

def held_layers(cfg: Dict[str, Any]) -> List[int]:
    """The published (1-based) numbers of the model's blocks."""
    n = cfg["num_hidden_layers"]
    return list(cfg.get("held_layers") or range(1, n + 1))[:n]


def layers_of(params: Dict[str, Any], cfg: Dict[str, Any],
              ) -> List[Tuple[bool, bool, Dict[str, Any]]]:
    """[(mixes with KDA, FFN is the dense MLP, that layer's parameters)] in
    layer order."""
    kda_at = set(cfg["linear_attn_config"]["kda_layers"])
    dense = int(cfg.get("first_k_dense_replace") or 0)
    seen: Dict[str, int] = {}
    out = []
    for number in held_layers(cfg):
        is_kda, is_dense = number in kda_at, number <= dense
        kind = ("kda" if is_kda else "full") + ("_dense" if is_dense else "")
        i = seen.get(kind, 0)
        seen[kind] = i + 1
        out.append((is_kda, is_dense,
                    {k: w[i] for k, w in params["layers"][kind].items()}))
    return out


def block(h, is_kda: bool, dense: bool, cfg: Dict[str, Any], lp,
          wrong: FrozenSet[str] = NONE):
    eps = eps_of(cfg)
    u = rms(h, lp["ln1"], eps)
    h = h + (kda(u, cfg, lp, wrong) if is_kda
             else attention(u, cfg, lp, wrong))
    u = rms(h, lp["ln2"], eps)
    if dense:
        return h + swiglu(u, lp["w_gate"], lp["w_up"], lp["w_down"], wrong)
    return h + moe(u, cfg, lp, wrong)


def hidden(params, cfg: Dict[str, Any], tokens,
           wrong: FrozenSet[str] = NONE):
    """[T, D]: the residual stream behind the last block."""
    h = f32(params["embedding"][tokens])
    for is_kda, dense, lp in layers_of(params, cfg):
        h = block(h, is_kda, dense, cfg, lp, wrong)
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
