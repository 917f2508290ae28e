"""Plain reference: the forward pass, the PPO logprobs and the loss of
Qwen3-Next-80B-A3B-Instruct (``Qwen/Qwen3-Next-80B-A3B-Instruct``,
``model_type`` qwen3_next) in straightforward float32 ``jax.numpy`` at
``precision="highest"`` — no kernel, no chunk, no WY transform, no cache,
and no import from ``areal_tpu``. Written from the published
``config.json`` keys and HF's ``modeling_qwen3_next.py`` as recalled (the
configuration file's ``assumed`` lists what that rests on). ONE document
at a time: nothing is packed, so there is no reset code — the state
simply starts at zero and a tap before the first token reads 0.

    rms(x, w) = x / sqrt(mean(x²) + eps) · (1 + w)        zero-centred
    block l:  h = h + mix_l(rms(h, w1));  h = h + moe(rms(h, w2))
    logits = rms(h_L, w_f) W_head

``mix_l`` is gated softmax attention where ``(l + 1) %
full_attention_interval == 0`` (l the PUBLISHED index: ``first_layer_index``
+ the layer's index here) and a Gated DeltaNet mixer otherwise.

Gated DeltaNet, ``u`` [T, D]; G key heads of dk, H = r G value heads of
dv, value head i reads key head i // r::

    [q | k | v | z] = u W_qkvz;  [b | a] = u W_ba
    [q | k | v]_t = silu(sum_{j<K} w[K-1-j] [q | k | v]_{t-j})   no bias
    beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)
    q = q rsqrt(sum q² + 1e-6) dk^-1/2;  k = k rsqrt(sum k² + 1e-6)
    S <- exp(g_t) S;  d_t = beta_t (v_t - S^T k_t);  S <- S + k_t d_t^T
    o_t = S^T q_t                              S_{-1} = 0, a token at a
    y = o / sqrt(mean(o²) + eps) w ⊙ silu(z)   time (lax.scan); w plain
    mix = y W_o

Gated attention, 16 q / 2 kv heads of 256: ``q, gate = u W_q, u W_g``; q
and k normed a head (zero-centred); RoPE at ``rope_theta`` on the FIRST
``partial_rotary_factor · head_dim`` dims of each head, rotate-half inside
them; ``softmax(q k^T / sqrt(head_dim)) v`` causal; ``mix = (o ⊙
sigmoid(gate)) W_o``.

Expert layer: ``p = softmax(u W_r)`` over all the published experts; the
``num_experts_per_tok`` largest, renormalised to sum 1; the sum over the
chosen experts HELD HERE of ``g_e W_down,e (silu(u W_gate,e) ⊙ u
W_up,e)``; plus ``sigmoid(u w_sg) · shared(u)``.

A SHARE (the configuration file's cut): ``num_experts`` of the
``num_routed_experts`` the router scores are held, from
``expert_shard_index · num_experts`` on — an expert held elsewhere adds
nothing here and nothing stands in for it; the vocabulary is a slice.

Weights are read in the layout the program stores them in, which is data,
not code: ``embedding`` [V, D], ``final_ln`` [D], ``lm_head`` [D, V] and
``layers/<kind>/<name>`` stacked over the layers of that kind in order —
``gdn``: ln1, ln2, gdn_qkvz [D, q | k | v | z] (each part whole, heads in
order — NOT HF's interleaving by key head), gdn_ba [D, b | a], gdn_conv
[K, q | k | v], gdn_dt_bias, gdn_A_log [H], gdn_norm [dv], gdn_out;
``full``: ln1, ln2, wq, wg, wk, wv, wo, q_norm, k_norm; both: router
[D, routed], e_gate / e_up [held, D, F], e_down [held, F, D], s_gate /
s_up [D, Fs], s_down [Fs, D], s_sig [D, 1].

``wrong``: names of the deliberate faults ``check_limits_qwen3_next.py``
shows the tolerances refuse (``WRONG``); empty = the model as published.
"""

from __future__ import annotations

from typing import Any, Dict, FrozenSet, Optional

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512
L2_EPS = 1e-6
WRONG = (
    "state_in_bfloat16",  # the rule's state rounded after every token
    "beta_left_at_1",
    "no_l2_norm_of_q_and_k",
    "rope_on_all_dims",
    "norm_weight_without_1_plus",
    "no_shared_expert_gate",
    "gates_not_renormalised",
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
    return cfg.get("rms_norm_eps", 1e-6)


def rms(x, w, eps, wrong: FrozenSet[str] = NONE):
    w = f32(w) if "norm_weight_without_1_plus" in wrong else 1.0 + f32(w)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def is_full(cfg: Dict[str, Any], layer: int) -> bool:
    """Whether layer ``layer`` of the configuration as it is run is a
    full-attention block."""
    published = int(cfg.get("first_layer_index", 0)) + layer
    return (published + 1) % cfg["full_attention_interval"] == 0


# ---------------- Gated DeltaNet ----------------

def conv(x, w):
    """[T, C] -> [T, C]: depthwise, causal, no bias; ``w[K-1]`` multiplies
    the token itself; a tap before the document's first token reads 0."""
    K, T = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, x.shape[1])), x], 0)
    return sum(w[K - 1 - j] * padded[K - 1 - j:K - 1 - j + T]
               for j in range(K))


def delta_rule(q, k, v, g, beta, wrong: FrozenSet[str] = NONE):
    """The recurrence, a token at a time. q / k [T, H, dk] (each value
    head's key head already chosen), v [T, H, dv], g / beta [T, H] ->
    o [T, H, dv]."""
    def step(S, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        S = jnp.exp(g_t)[:, None, None] * S
        d = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t,
                                             precision=HI))
        S = S + k_t[:, :, None] * d[:, None, :]
        if "state_in_bfloat16" in wrong:
            # reduce_precision, not a pair of casts: the TPU compiler may
            # drop a cast down and back up as excess precision it may keep
            S = jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
        return S, jnp.einsum("hkv,hk->hv", S, q_t, precision=HI)

    S0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    return jax.lax.scan(step, S0, (q, k, v, g, beta))[1]


def gdn_sizes(cfg: Dict[str, Any]):
    """(G, H, dk, dv, K)."""
    return (cfg["linear_num_key_heads"], cfg["linear_num_value_heads"],
            cfg["linear_key_head_dim"], cfg["linear_value_head_dim"],
            cfg["linear_conv_kernel_dim"])


def gdn_rule_inputs(u, cfg: Dict[str, Any], lp, wrong: FrozenSet[str] = NONE):
    """(q, k, v, g, beta, z): what the rule and the gated norm read."""
    G, H, dk, dv, _ = gdn_sizes(cfg)
    T, kd = u.shape[0], G * dk
    qkvz = mm(u, lp["gdn_qkvz"], wrong)
    qkv, z = qkvz[:, :2 * kd + H * dv], qkvz[:, 2 * kd + H * dv:]
    b, a = jnp.split(mm(u, lp["gdn_ba"], wrong), 2, axis=-1)
    qkv = jax.nn.silu(conv(qkv, f32(lp["gdn_conv"])))
    q = qkv[:, :kd].reshape(T, G, dk)
    k = qkv[:, kd:2 * kd].reshape(T, G, dk)
    v = qkv[:, 2 * kd:].reshape(T, H, dv)
    if "no_l2_norm_of_q_and_k" not in wrong:
        q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + L2_EPS)
        k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + L2_EPS)
    q = q * dk ** -0.5
    of_head = jnp.arange(H) // (H // G)  # the key head a value head reads
    beta = (jnp.ones_like(b) if "beta_left_at_1" in wrong
            else jax.nn.sigmoid(b))
    g = -jnp.exp(f32(lp["gdn_A_log"])) * jax.nn.softplus(
        a + f32(lp["gdn_dt_bias"]))
    return q[:, of_head], k[:, of_head], v, g, beta, z.reshape(T, H, dv)


def gdn(u, cfg: Dict[str, Any], lp, wrong: FrozenSet[str] = NONE):
    """One Gated DeltaNet mixer on ``u`` [T, D]."""
    q, k, v, g, beta, z = gdn_rule_inputs(u, cfg, lp, wrong)
    o = delta_rule(q, k, v, g, beta, wrong)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps_of(cfg))
    y = o * f32(lp["gdn_norm"]) * jax.nn.silu(z)
    return mm(y.reshape(u.shape[0], -1), lp["gdn_out"], wrong)


# ---------------- gated attention ----------------

def rope(x, theta: float, dims: int):
    """x [T, H, Dh]: the first ``dims`` of each head turned (rotate-half
    inside them), positions 0..T-1; the others untouched."""
    T = x.shape[0]
    inv = 1.0 / theta ** (jnp.arange(0, dims, 2, dtype=jnp.float32) / dims)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None]
    xr, half = x[..., :dims], dims // 2
    rot = jnp.concatenate([-xr[..., half:], xr[..., :half]], -1)
    return jnp.concatenate([xr * cos + rot * sin, x[..., dims:]], -1)


def attention(u, cfg: Dict[str, Any], lp, wrong: FrozenSet[str] = NONE):
    nq, nkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    T, eps = u.shape[0], eps_of(cfg)
    dims = dh if "rope_on_all_dims" in wrong else int(
        dh * cfg.get("partial_rotary_factor", 1.0))
    q = rms(mm(u, lp["wq"], wrong).reshape(T, nq, dh), lp["q_norm"], eps,
            wrong)
    k = rms(mm(u, lp["wk"], wrong).reshape(T, nkv, dh), lp["k_norm"], eps,
            wrong)
    v = mm(u, lp["wv"], wrong).reshape(T, nkv, dh)
    q = rope(q, cfg["rope_theta"], dims).reshape(T, nkv, nq // nkv, dh)
    k = rope(k, cfg["rope_theta"], dims)
    pk = jnp.arange(T)[None, :]
    out = []
    for t0 in range(0, T, QUERY_BLOCK):
        pq = jnp.arange(t0, min(t0 + QUERY_BLOCK, T))[:, None]
        s = jnp.einsum("tkgd,skd->kgts", q[t0:t0 + QUERY_BLOCK], k,
                       precision=HI) * dh ** -0.5
        p = jax.nn.softmax(jnp.where((pk <= pq)[None, None], s, -jnp.inf), -1)
        out.append(jnp.einsum("kgts,skd->tkgd", p, v, precision=HI))
    o = jnp.concatenate(out, 0).reshape(T, nq * dh)
    return mm(o * jax.nn.sigmoid(mm(u, lp["wg"], wrong)), lp["wo"], wrong)


# ---------------- the expert layer ----------------

def swiglu(x, w_gate, w_up, w_down, wrong: FrozenSet[str] = NONE):
    return mm(jax.nn.silu(mm(x, w_gate, wrong)) * mm(x, w_up, wrong),
              w_down, wrong)


def gates(x, cfg: Dict[str, Any], router, wrong: FrozenSet[str] = NONE):
    """[T, D] -> the dense [T, routed] gate matrix: each chosen expert's
    probability over the chosen ones' sum, 0 elsewhere."""
    p = jax.nn.softmax(mm(x, router), -1)
    idx = jnp.argsort(-p, axis=-1)[:, :cfg["num_experts_per_tok"]]
    top = jnp.take_along_axis(p, idx, -1)
    if cfg.get("norm_topk_prob", True) and (
            "gates_not_renormalised" not in wrong):
        top = top / jnp.sum(top, -1, keepdims=True)
    return jnp.zeros_like(p).at[jnp.arange(x.shape[0])[:, None], idx].set(top)


def first_held(cfg: Dict[str, Any]) -> int:
    return int(cfg.get("expert_shard_index", 0) or 0) * cfg["num_experts"]


def routed(x, cfg: Dict[str, Any], lp, wrong: FrozenSet[str] = NONE):
    """The held experts' part: every held expert on every token, times its
    gate (0 where the token did not choose it)."""
    g = gates(x, cfg, lp["router"], wrong)
    first = first_held(cfg)
    out = jnp.zeros_like(x)
    for e in range(cfg["num_experts"]):
        out = out + g[:, first + e, None] * swiglu(
            x, lp["e_gate"][e], lp["e_up"][e], lp["e_down"][e], wrong)
    return out


def shared(x, lp, wrong: FrozenSet[str] = NONE):
    y = swiglu(x, lp["s_gate"], lp["s_up"], lp["s_down"], wrong)
    if "no_shared_expert_gate" in wrong:
        return y
    return jax.nn.sigmoid(mm(x, lp["s_sig"])) * y


def moe(x, cfg: Dict[str, Any], lp, wrong: FrozenSet[str] = NONE):
    return routed(x, cfg, lp, wrong) + shared(x, lp, wrong)


# ---------------- the model ----------------

MIXERS = {"gdn": gdn, "full": attention}


def layers_of(params: Dict[str, Any], cfg: Dict[str, Any]):
    """[(kind, that layer's parameters)] in layer order."""
    seen: Dict[str, int] = {}
    out = []
    for layer in range(cfg["num_hidden_layers"]):
        kind = "full" if is_full(cfg, layer) else "gdn"
        i = seen.get(kind, 0)
        seen[kind] = i + 1
        out.append((kind, {k: w[i]
                           for k, w in params["layers"][kind].items()}))
    return out


def block(h, kind: str, cfg: Dict[str, Any], lp,
          wrong: FrozenSet[str] = NONE):
    eps = eps_of(cfg)
    h = h + MIXERS[kind](rms(h, lp["ln1"], eps, wrong), cfg, lp, wrong)
    return h + moe(rms(h, lp["ln2"], eps, wrong), cfg, lp, wrong)


def hidden(params, cfg: Dict[str, Any], tokens,
           wrong: FrozenSet[str] = NONE):
    """[T, D]: the residual stream behind the last block."""
    h = f32(params["embedding"][tokens])
    for kind, lp in layers_of(params, cfg):
        h = block(h, kind, cfg, lp, wrong)
    return h


def head(params, cfg: Dict[str, Any], h, wrong: FrozenSet[str] = NONE):
    return mm(rms(h, params["final_ln"], eps_of(cfg), wrong),
              params["lm_head"], wrong)


def logits(params, cfg: Dict[str, Any], tokens,
           wrong: FrozenSet[str] = NONE) -> jnp.ndarray:
    """[T, V] float32 logits of ONE document ``tokens`` [T]. ``cfg`` holds
    the HF config keys of the configuration file."""
    return head(params, cfg, hidden(params, cfg, tokens, wrong), wrong)


def logprobs_of(lg, tokens) -> jnp.ndarray:
    lp = jax.nn.log_softmax(lg[:-1], -1)
    return jnp.take_along_axis(lp, tokens[1:, None], -1)[:, 0]


def token_logprobs(params, cfg, tokens,
                   wrong: FrozenSet[str] = NONE) -> jnp.ndarray:
    """[T-1]: log p(tokens[t+1] | tokens[:t+1]) for t = 0..T-2 — what the
    PPO actor's inference pass returns for a document."""
    tokens = jnp.asarray(tokens, jnp.int32)
    return logprobs_of(logits(params, cfg, tokens, wrong), tokens)


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
