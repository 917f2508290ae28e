"""Plain reference: the forward pass of Nemotron 3 Super (NVIDIA
``NVIDIA-Nemotron-3-Super-120B-A12B-BF16``, ``model_type`` nemotron_h) in
straightforward float32 ``jax.numpy`` at ``precision="highest"`` — no
kernel, no chunking, no sorting, and no import from ``areal_tpu``. Written
from the published ``config.json`` keys. ONE document at a time: nothing
is packed, so there is no reset code — the state simply starts at zero.

Every layer is one mixer, ``h <- h + f(rms(h, ln))``, ``f`` by the letter
of ``hybrid_override_pattern``; after the last layer ``rms(h, final_ln)``
and the untied head.

``M`` — Mamba-2, ``u`` [T, D]; H heads of P, G groups, state N, kernel K::

    [z | xBC | dt] = u W_in              d_inner | d_inner + 2 G N | H
    xBC_t = silu(b + sum_{j<K} w[K-1-j] xBC_{t-j})     taps before 0 read 0
    x [T, H, P], B [T, G, N], C [T, G, N] = split(xBC)
    D_t = softplus(dt_t + dt_bias)       A = -exp(A_log)        (a head)
    S_t = exp(D_t A) S_{t-1} + D_t x_t (x) B_t          S_{-1} = 0, a
    y_t = S_t C_t + D x_t                token at a time (lax.scan)
    y = rms_over_each_group(y silu(z)) norm_w          gate first
    f = y W_out                          head h reads group h // (H / G)

``*`` — attention: ``q, k, v = u Wq, u Wk, u Wv``, NO position embedding,
causal softmax at scale 1/sqrt(head_dim), each group of q heads on its kv
head, ``f = a Wo``.

``E`` — LatentMoE: ``s = sigmoid(u W_r)`` over all the published experts;
chosen = the ``num_experts_per_tok`` largest of ``s + bias``; gates ``g =
s[chosen] / sum(s[chosen]) * routed_scaling_factor``; ``v = u W_fc1``
(hidden -> latent); ``r = sum_e g_e relu(v W_up,e)^2 W_down,e``; ``f = r
W_fc2 + relu(u W_s,up)^2 W_s,down`` (the shared expert, on every token).
Every HELD expert runs on every token, weighted by its gate or by 0.

A SHARE (the configuration file's cut): ``n_routed_experts`` experts of
``num_routed_experts`` are held, those from ``expert_shard_index *
n_routed_experts`` on; ``mamba_num_heads`` / ``n_groups`` /
``num_attention_heads`` / ``num_key_value_heads`` are the heads held. What
the functions return is then the PARTIAL result this chip computes: its
heads' part of the output projections' sums, its experts' part of ``r``.

Left out: the multi-token-prediction module (``num_nextn_predict_layers``).

Weights are read in the layout the program stores them in, which is data,
not code: ``embedding`` [V, D], ``final_ln`` [D], ``lm_head`` [D, V], and
``layers/<kind>/<name>`` stacked over the layers of that kind in pattern
order — ``mamba``: ln, in_proj [D, .], conv_w [K, C], conv_b, dt_bias,
A_log, D, norm, out_proj; ``moe_only``: ln, router [D, E], router_bias
[E], latent_down [D, L], latent_up [L, D], e_up [held, L, F], e_down
[held, F, L], s_up [D, Fs], s_down [Fs, D]; ``attention_only``: ln, wq,
wk, wv, wo.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512
KINDS = {"M": "mamba", "E": "moe_only", "*": "attention_only"}


def f32(a):
    return jnp.asarray(a, jnp.float32)


def mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def eps_of(cfg: Dict[str, Any]) -> float:
    return cfg.get("norm_eps", cfg.get("layer_norm_epsilon", 1e-5))


def relu2(x):
    return jnp.square(jnp.maximum(x, 0))


def expert_act(cfg: Dict[str, Any]):
    return {"relu2": relu2, "silu": jax.nn.silu}[
        cfg.get("mlp_hidden_act", "relu2")]


# ---------------- M ----------------

def conv(xBC, w, b):
    """[T, C] -> [T, C]: depthwise, causal; ``w[K-1]`` multiplies the token
    itself; a tap before the document's first token reads 0."""
    K, T = w.shape[0], xBC.shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, xBC.shape[1])), xBC], 0)
    return b + sum(w[K - 1 - j] * padded[K - 1 - j:K - 1 - j + T]
                   for j in range(K))


def scan(x, dt, A, Bm, Cm, state_dtype=jnp.float32):
    """The recurrence, a token at a time. x [T, H, P], dt [T, H], A [H],
    Bm / Cm [T, H, N] (each head's group already chosen) -> y [T, H, P]."""
    def step(S, inp):
        x_t, dt_t, B_t, C_t = inp
        S = (jnp.exp(dt_t * A)[:, None, None] * S
             + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
        S = S.astype(state_dtype).astype(jnp.float32)
        return S, jnp.einsum("hpn,hn->hp", S, C_t, precision=HI)

    S0 = jnp.zeros((x.shape[1], x.shape[2], Bm.shape[-1]), jnp.float32)
    return jax.lax.scan(step, S0, (x, dt, Bm, Cm))[1]


def gated_norm(y, z, w, groups: int, eps: float):
    """[T, d_inner]: the gate first, then RMSNorm over each group."""
    y = y * jax.nn.silu(z)
    T, di = y.shape
    y = y.reshape(T, groups, di // groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
    return y.reshape(T, di) * w


def mamba(u, cfg: Dict[str, Any], lp: Dict[str, Any]):
    """One Mamba-2 mixer on ``u`` [T, D] (the heads held: their part of
    the output projection's sum)."""
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N = cfg["n_groups"], cfg["ssm_state_size"]
    T, di = u.shape[0], H * P
    z, xBC, dt = jnp.split(mm(u, f32(lp["in_proj"])),
                           [di, 2 * di + 2 * G * N], axis=-1)
    xBC = jax.nn.silu(conv(xBC, f32(lp["conv_w"]), f32(lp["conv_b"])))
    x, Bm, Cm = jnp.split(xBC, [di, di + G * N], axis=-1)
    x = x.reshape(T, H, P)
    of_head = jnp.arange(H) // (H // G)  # the group a head reads
    Bm = Bm.reshape(T, G, N)[:, of_head]
    Cm = Cm.reshape(T, G, N)[:, of_head]
    dt = jax.nn.softplus(dt + f32(lp["dt_bias"]))
    y = scan(x, dt, -jnp.exp(f32(lp["A_log"])), Bm, Cm)
    y = y + f32(lp["D"])[:, None] * x
    y = gated_norm(y.reshape(T, di), z, f32(lp["norm"]), G, eps_of(cfg))
    return mm(y, f32(lp["out_proj"]))


# ---------------- * ----------------

def attention(u, cfg: Dict[str, Any], lp: Dict[str, Any]):
    """Causal softmax attention with no position embedding, a block of
    queries at a time against all keys."""
    nq, nkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    T = u.shape[0]
    q = mm(u, f32(lp["wq"])).reshape(T, nkv, nq // nkv, dh)
    k = mm(u, f32(lp["wk"])).reshape(T, nkv, dh)
    v = mm(u, f32(lp["wv"])).reshape(T, nkv, dh)
    pk = jnp.arange(T)[None, :]
    out = []
    for t0 in range(0, T, QUERY_BLOCK):
        pq = jnp.arange(t0, min(t0 + QUERY_BLOCK, T))[:, None]
        s = jnp.einsum("tkgd,skd->kgts", q[t0:t0 + QUERY_BLOCK], k,
                       precision=HI) / jnp.sqrt(jnp.float32(dh))
        p = jax.nn.softmax(jnp.where((pk <= pq)[None, None], s, -jnp.inf), -1)
        out.append(jnp.einsum("kgts,skd->tkgd", p, v, precision=HI))
    return mm(jnp.concatenate(out, 0).reshape(T, nq * dh), f32(lp["wo"]))


# ---------------- E ----------------

def gates(scores, bias, top_k: int, norm: bool, scale: float):
    """[T, E] sigmoid scores -> the dense [T, E] gate matrix: the score of
    each of the ``top_k`` largest of score + bias (over their sum when
    ``norm``) times ``scale``, 0 elsewhere."""
    idx = jnp.argsort(-(scores + bias), axis=-1)[:, :top_k]
    chosen = jnp.sum(jax.nn.one_hot(idx, scores.shape[-1],
                                    dtype=scores.dtype), axis=1)
    g = scores * chosen
    if norm:
        g = g / jnp.sum(g, -1, keepdims=True)
    return g * scale


def experts(v, g, w_up, w_down, act):
    """[T, L] -> [T, L]: every expert whose weights are given on every
    token, weighted by its column of ``g`` [T, held]; not gated."""
    y = jnp.zeros_like(v)
    for e in range(g.shape[-1]):
        y = y + g[:, e:e + 1] * mm(act(mm(v, f32(w_up[e]))), f32(w_down[e]))
    return y


def held_experts(cfg: Dict[str, Any]):
    """(index of the first expert held, experts held)."""
    held = cfg["n_routed_experts"]
    return held * int(cfg.get("expert_shard_index") or 0), held


def routed(u, cfg: Dict[str, Any], lp: Dict[str, Any]):
    """The routed part of one expert layer on ``u`` [T, D] — on a share,
    the held experts' part of it."""
    g = gates(jax.nn.sigmoid(mm(u, f32(lp["router"]))),
              f32(lp["router_bias"]), cfg["num_experts_per_tok"],
              cfg["norm_topk_prob"], float(cfg["routed_scaling_factor"]))
    first, held = held_experts(cfg)
    v = mm(u, f32(lp["latent_down"]))
    r = experts(v, g[:, first:first + held], lp["e_up"], lp["e_down"],
                expert_act(cfg))
    return mm(r, f32(lp["latent_up"]))


def shared(u, cfg: Dict[str, Any], lp: Dict[str, Any]):
    return mm(expert_act(cfg)(mm(u, f32(lp["s_up"]))), f32(lp["s_down"]))


def moe(u, cfg: Dict[str, Any], lp: Dict[str, Any]):
    return routed(u, cfg, lp) + shared(u, cfg, lp)


# ---------------- the model ----------------

MIXERS = {"mamba": mamba, "moe_only": moe, "attention_only": attention}


def layers_of(params: Dict[str, Any], cfg: Dict[str, Any]):
    """[(kind, that layer's parameters)] in pattern order."""
    seen: Dict[str, int] = {}
    out = []
    pattern = cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]
    for letter in pattern:
        kind = KINDS[letter]
        i = seen.get(kind, 0)
        seen[kind] = i + 1
        out.append((kind, {k: w[i]
                           for k, w in params["layers"][kind].items()}))
    return out


def logits(params: Dict[str, Any], cfg: Dict[str, Any], tokens) -> jnp.ndarray:
    """[T, V] float32 logits of ONE document ``tokens`` [T]. ``cfg`` holds
    the HF config keys of the configuration file."""
    eps = eps_of(cfg)
    h = f32(params["embedding"][tokens])
    for kind, lp in layers_of(params, cfg):
        h = h + MIXERS[kind](_rms(h, f32(lp["ln"]), eps), cfg, lp)
    return mm(_rms(h, f32(params["final_ln"]), eps), f32(params["lm_head"]))


def token_logprobs(params, cfg, tokens) -> jnp.ndarray:
    """[T-1]: log p(tokens[t+1] | tokens[:t+1]) for t = 0..T-2."""
    tokens = jnp.asarray(tokens, jnp.int32)
    lp = jax.nn.log_softmax(logits(params, cfg, tokens)[:-1], -1)
    return jnp.take_along_axis(lp, tokens[1:, None], -1)[:, 0]


def loss(params, cfg, tokens) -> jnp.ndarray:
    """Mean negative logprob of one document (for the gradient tests)."""
    return -jnp.mean(token_logprobs(params, cfg, tokens))
