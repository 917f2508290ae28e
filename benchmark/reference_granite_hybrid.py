"""Plain reference: the forward pass, the PPO logprobs and the loss of
Granite 4.0-H Micro (IBM ``ibm-granite/granite-4.0-h-micro``,
``model_type`` granitemoehybrid, ``num_local_experts`` 0) in
straightforward float32 ``jax.numpy`` at ``precision="highest"`` — no
kernel, no chunking, and no import from ``areal_tpu``. Written from the
published ``config.json`` keys. ONE document at a time: nothing is packed,
so there is no reset code — the state simply starts at zero and a tap
before the first token reads 0.

    h_0 = embedding_multiplier E[ids]
    block l:  u = rms(h) w1;  h = h + residual_multiplier mix_l(u)
              u = rms(h) w2;  h = h + residual_multiplier
                                      (silu(u W_a) * (u W_b)) W_o
    logits = (rms(h_L) w_f) E^T / logits_scaling            E tied

``mix_l`` by ``layer_types[l]``.

``mamba`` — Mamba-2, ``u`` [T, D]; H heads of P, G groups, state N,
kernel K::

    [z | xBC | dt] = u W_in              d_inner | d_inner + 2 G N | H
    xBC_t = silu(b + sum_{j<K} w[K-1-j] xBC_{t-j})     taps before 0 read 0
    x [T, H, P], B [T, G, N], C [T, G, N] = split(xBC)
    D_t = softplus(dt_t + dt_bias)       A = -exp(A_log)        (a head)
    S_t = exp(D_t A) S_{t-1} + D_t x_t (x) B_t          S_{-1} = 0, a
    y_t = S_t C_t + D x_t                token at a time (lax.scan)
    y = rms_over_each_group(y silu(z)) norm_w          gate first
    mix = y W_out                        head h reads group h // (H / G)

``attention`` — ``q, k, v = u Wq, u Wk, u Wv`` (no bias), NO position
embedding, ``softmax(q k^T attention_multiplier) v`` causal, each group of
q heads on its kv head, ``mix = a Wo``. The scale is the config's
``attention_multiplier`` IN PLACE of 1/sqrt(head_dim).

A SHARE (the configuration file's cut): ``mamba_n_heads``,
``num_attention_heads`` and ``num_key_value_heads`` are the heads held
(``head_dim`` says the attention head's size, which the published file
leaves to hidden_size / num_attention_heads); the one B/C group is whole;
the layers are ``num_hidden_layers`` entries of ``layer_types`` from
``first_layer_index`` on.
What ``mamba`` and ``attention`` return is then the PARTIAL result this
chip computes — its heads' part of the output projection's sum — with the
gated norm's mean square over the held channels (:func:`gated_norm` takes
the sum of squares of ALL the deployment's channels where a caller has
it: the share test in tests/test_granite_parity.py).

Weights are read in the layout the program stores them in, which is data,
not code: ``embedding`` [V, D], ``final_ln`` [D], and ``layers/<kind>/
<name>`` stacked over the layers of that kind in pattern order — ``ssd``
(HF ``mamba``): ln1, ln2, in_proj [D, .], conv_w [K, C], conv_b, dt_bias,
A_log, D, norm, out_proj, w_gate, w_up, w_down; ``full`` (HF
``attention``): ln1, ln2, wq, wk, wv, wo, w_gate, w_up, w_down.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512
KINDS = {"mamba": "ssd", "attention": "full"}


def f32(a):
    return jnp.asarray(a, jnp.float32)


def mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def eps_of(cfg: Dict[str, Any]) -> float:
    return cfg.get("rms_norm_eps", 1e-5)


def attention_head_dim(cfg: Dict[str, Any]) -> int:
    return cfg.get("head_dim") or (
        cfg["hidden_size"] // cfg["num_attention_heads"])


# ---------------- mamba ----------------

def conv(xBC, w, b):
    """[T, C] -> [T, C]: depthwise, causal; ``w[K-1]`` multiplies the token
    itself; a tap before the document's first token reads 0."""
    K, T = w.shape[0], xBC.shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, xBC.shape[1])), xBC], 0)
    return b + sum(w[K - 1 - j] * padded[K - 1 - j:K - 1 - j + T]
                   for j in range(K))


def scan(x, dt, A, Bm, Cm):
    """The recurrence, a token at a time. x [T, H, P], dt [T, H], A [H],
    Bm / Cm [T, H, N] (each head's group already chosen) -> y [T, H, P]."""
    def step(S, inp):
        x_t, dt_t, B_t, C_t = inp
        S = (jnp.exp(dt_t * A)[:, None, None] * S
             + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
        return S, jnp.einsum("hpn,hn->hp", S, C_t, precision=HI)

    S0 = jnp.zeros((x.shape[1], x.shape[2], Bm.shape[-1]), jnp.float32)
    return jax.lax.scan(step, S0, (x, dt, Bm, Cm))[1]


def gated_norm(y, z, w, groups: int, eps: float, sum_sq=None, width=None):
    """[T, d_inner]: the gate first, then RMSNorm over each group. With
    ``sum_sq`` [T, groups] and ``width`` the mean square is that sum over
    ``width`` channels (a deployment's, of which these are a share)."""
    y = y * jax.nn.silu(z)
    T, di = y.shape
    y = y.reshape(T, groups, di // groups)
    if sum_sq is None:
        mean_sq = jnp.mean(y * y, -1, keepdims=True)
    else:
        mean_sq = (sum_sq / width)[..., None]
    y = y * jax.lax.rsqrt(mean_sq + eps)
    return y.reshape(T, di) * w


def mamba_gated(u, cfg: Dict[str, Any], lp: Dict[str, Any]):
    """(y, z) [T, d_inner] each: the scan's output with the skip, and the
    gate's input — what the gated norm reads."""
    H, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    G, N = cfg["mamba_n_groups"], cfg["mamba_d_state"]
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
    return y.reshape(T, di), z


def mamba(u, cfg: Dict[str, Any], lp: Dict[str, Any], sum_sq=None,
          width=None):
    """One Mamba-2 mixer on ``u`` [T, D] (the heads held: their part of
    the output projection's sum)."""
    y, z = mamba_gated(u, cfg, lp)
    y = gated_norm(y, z, f32(lp["norm"]), cfg["mamba_n_groups"], eps_of(cfg),
                   sum_sq, width)
    return mm(y, f32(lp["out_proj"]))


# ---------------- attention ----------------

def attention(u, cfg: Dict[str, Any], lp: Dict[str, Any]):
    """Causal softmax attention with no position embedding at the scale
    ``attention_multiplier``, a block of queries at a time against all
    keys."""
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = attention_head_dim(cfg)
    scale = cfg.get("attention_multiplier") or dh ** -0.5
    T = u.shape[0]
    q = mm(u, f32(lp["wq"])).reshape(T, nkv, nq // nkv, dh)
    k = mm(u, f32(lp["wk"])).reshape(T, nkv, dh)
    v = mm(u, f32(lp["wv"])).reshape(T, nkv, dh)
    pk = jnp.arange(T)[None, :]
    out = []
    for t0 in range(0, T, QUERY_BLOCK):
        pq = jnp.arange(t0, min(t0 + QUERY_BLOCK, T))[:, None]
        s = jnp.einsum("tkgd,skd->kgts", q[t0:t0 + QUERY_BLOCK], k,
                       precision=HI) * scale
        p = jax.nn.softmax(jnp.where((pk <= pq)[None, None], s, -jnp.inf), -1)
        out.append(jnp.einsum("kgts,skd->tkgd", p, v, precision=HI))
    return mm(jnp.concatenate(out, 0).reshape(T, nq * dh), f32(lp["wo"]))


def mlp(u, lp: Dict[str, Any]):
    """The gated MLP (HF ``shared_mlp``: ``input_linear`` = [W_a | W_b])."""
    return mm(jax.nn.silu(mm(u, f32(lp["w_gate"]))) * mm(u, f32(lp["w_up"])),
              f32(lp["w_down"]))


# ---------------- the model ----------------

MIXERS = {"ssd": mamba, "full": attention}


def layer_types(cfg: Dict[str, Any]):
    """The ``num_hidden_layers`` entries of ``layer_types`` that are run:
    from ``first_layer_index`` on (a cut in depth that starts inside the
    published stack; 0 where the key is absent)."""
    first = int(cfg.get("first_layer_index", 0))
    return cfg["layer_types"][first:first + cfg["num_hidden_layers"]]


def layers_of(params: Dict[str, Any], cfg: Dict[str, Any]):
    """[(kind, that layer's parameters)] in pattern order."""
    seen: Dict[str, int] = {}
    out = []
    for name in layer_types(cfg):
        kind = KINDS[name]
        i = seen.get(kind, 0)
        seen[kind] = i + 1
        out.append((kind, {k: w[i]
                           for k, w in params["layers"][kind].items()}))
    return out


def block(h, kind: str, cfg: Dict[str, Any], lp: Dict[str, Any]):
    eps, m = eps_of(cfg), cfg.get("residual_multiplier", 1.0)
    h = h + m * MIXERS[kind](_rms(h, f32(lp["ln1"]), eps), cfg, lp)
    return h + m * mlp(_rms(h, f32(lp["ln2"]), eps), lp)


def hidden(params: Dict[str, Any], cfg: Dict[str, Any], tokens):
    """[T, D]: the residual stream behind the last block."""
    h = cfg.get("embedding_multiplier", 1.0) * f32(params["embedding"][tokens])
    for kind, lp in layers_of(params, cfg):
        h = block(h, kind, cfg, lp)
    return h


def head(params: Dict[str, Any], cfg: Dict[str, Any], h):
    w = params["embedding"].T if cfg.get(
        "tie_word_embeddings", True) else params["lm_head"]
    return mm(_rms(h, f32(params["final_ln"]), eps_of(cfg)),
              f32(w)) / cfg.get("logits_scaling", 1.0)


def logits(params: Dict[str, Any], cfg: Dict[str, Any], tokens) -> jnp.ndarray:
    """[T, V] float32 logits of ONE document ``tokens`` [T]. ``cfg`` holds
    the HF config keys of the configuration file."""
    return head(params, cfg, hidden(params, cfg, tokens))


def token_logprobs(params, cfg, tokens) -> jnp.ndarray:
    """[T-1]: log p(tokens[t+1] | tokens[:t+1]) for t = 0..T-2 — what the
    PPO actor's inference pass returns for a document."""
    tokens = jnp.asarray(tokens, jnp.int32)
    lp = jax.nn.log_softmax(logits(params, cfg, tokens)[:-1], -1)
    return jnp.take_along_axis(lp, tokens[1:, None], -1)[:, 0]


def loss(params, cfg, tokens, weights: Optional[Any] = None) -> jnp.ndarray:
    """Negative logprob of one document, summed under ``weights`` [T-1]
    or (None) averaged: ``jax.grad`` of it is the gradient tests' oracle,
    the tied embedding's from both of its uses."""
    lp = token_logprobs(params, cfg, tokens)
    if weights is None:
        return -jnp.mean(lp)
    return -jnp.sum(lp * jnp.asarray(weights, jnp.float32))
