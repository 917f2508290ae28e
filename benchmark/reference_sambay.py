"""Plain reference: the forward pass of Phi-4-mini-flash-reasoning
(microsoft, ``model_type`` phi4flash; the SambaY decoder-hybrid-decoder of
arXiv 2507.06607 with differential attention) in straightforward float32
``jax.numpy`` at ``precision="highest"`` — no kernel, no chunking, no
packing, and no import from ``areal_tpu``. Written from the published
``config.json`` keys and the equations in the configuration file's
``assumed``. ONE document at a time: nothing is packed, so there is no
reset code — a state simply starts at zero.

Every layer l is a whole block under LayerNorm (weight AND bias)::

    h <- h + mix_l(LN1(h));   h <- h + W_down(silu(W_gate a) * W_up a),  a = LN2(h)

and behind the last one ``LN_f(h)`` and the tied head. The mixer by the
layer's letter (``layer_pattern``; for the whole model :func:`pattern_of`
derives it from ``num_hidden_layers`` and ``mb_per_layer``), D = hidden:

``M`` — Mamba-1 (d_inner = 2 D, N = 16 states, K = 4 taps, dt_rank =
ceil(D / 16))::

    [x | z] = u W_in                                   d_inner | d_inner
    x_t = silu(b_c + sum_{j<K} w[K-1-j] x_{t-j})       taps before 0 read 0
    [delta | B | C] = x W_x                            dt_rank | N | N
    Delta_t = softplus(delta_t W_dt + b_dt)            [d_inner]
    A = -exp(A_log)                                    [d_inner, N]
    h_t = exp(Delta_t A) h_{t-1} + (Delta_t x_t) (x) B_t      h_{-1} = 0, a
    y_t = h_t C_t + D_skip x_t                         token at a time
    mix = (y silu(z)) W_out

  The LAST M before the first G also hands on ``m = y`` — the scan's
  output BEFORE the gate: the memory.

``S`` / ``F`` — differential attention, window ``sliding_window`` / none.
``q = u W_q + b_q`` as 2P heads of d, ``k`` as 2J heads of d, ``v`` as J
values of 2 d (P = heads / 2, J = kv heads / 2); q's pair p = (q_{2p},
q_{2p+1}) reads k's pair (k_{2j}, k_{2j+1}) and value v_j, j = p // (P/J)::

    o_p = softmax(q1 k1^T / sqrt(d)) v - lambda softmax(q2 k2^T / sqrt(d)) v
    lambda = exp(l_q1 . l_k1) - exp(l_q2 . l_k2) + lambda_init
    lambda_init = 0.8 - 0.6 exp(-0.3 l)      l = first_layer_index + layer
    mix = concat_p(rms(o_p, subln) (1 - lambda_init)) W_o + b_o

  No position embedding. The F before the first X hands on its ``k, v``.

``G`` — gated memory unit: ``mix = (m silu(u W_1)) W_2``; nothing across
tokens. ``X`` — cross attention: ``q = u W_q + b_q`` against the K, V the
F layer handed on (causal, no window), lambda, sub-norm and W_o its own.

Weights are read in the layout the program stores them in, which is data,
not code: ``embedding`` [V, D], ``final_ln``, ``final_ln_b`` [D], and
``layers/<kind>/<name>`` stacked over the layers of that kind in pattern
order, kinds ``s6``, ``sliding``, ``full``, ``gmu``, ``cross``; every
block ln1, ln1_b, ln2, ln2_b, w_gate, w_up [D, F], w_down [F, D]; ``s6``:
in_proj [D, 2 d_inner], conv_w [K, d_inner], conv_b, x_proj [d_inner,
dt_rank + 2 N], dt_proj [dt_rank, d_inner], dt_bias, A_log [d_inner, N],
D, out_proj; ``gmu``: gmu_in [D, d_inner], gmu_out; attention: wq, bq,
wo, bo, lambda_q1, lambda_k1, lambda_q2, lambda_k2 [d], subln [2 d], and
(not ``cross``) wk, bk, wv, bv.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
KINDS = {"M": "s6", "S": "sliding", "F": "full", "G": "gmu", "X": "cross"}


def f32(a):
    return jnp.asarray(a, jnp.float32)


def mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def eps_of(cfg: Dict[str, Any]) -> float:
    return cfg.get("layer_norm_eps", 1e-5)


def pattern_of(cfg: Dict[str, Any]) -> str:
    """A letter a layer: the file's ``layer_pattern`` (a cut in depth), or
    the published rule."""
    n, every = cfg["num_hidden_layers"], cfg["mb_per_layer"]
    if cfg.get("layer_pattern"):
        return cfg["layer_pattern"][:n]
    out = ""
    for i in range(n):
        if i % every == 0:
            out += "M" if i <= n // 2 else "G"
        else:
            out += "S" if i < n // 2 else "F" if i == n // 2 + 1 else "X"
    return out


def memory_source(pattern: str) -> int:
    """The layer whose scan output the G layers gate."""
    return pattern.rindex("M", 0, pattern.index("G"))


def kv_source(pattern: str) -> int:
    """The layer whose K, V the X layers attend over."""
    return pattern.rindex("F", 0, pattern.index("X"))


# ---- M ----

def conv(x, w, b):
    """Depthwise causal convolution, ``w[K-1]`` on the token itself."""
    K, T = w.shape[0], x.shape[0]
    out = b + x * w[K - 1]
    for j in range(1, K):
        out = out + jnp.pad(x, ((j, 0), (0, 0)))[:T] * w[K - 1 - j]
    return out


def scan(x, dt, A, Bm, Cm):
    """The recurrence a token at a time: x, dt [T, di]; A [di, N]; Bm, Cm
    [T, N]. Returns y [T, di] (without the skip)."""
    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        h = jnp.exp(dt_t[:, None] * A) * h + (dt_t * x_t)[:, None] * b_t
        return h, jnp.sum(h * c_t, -1)

    _, y = jax.lax.scan(step, jnp.zeros(A.shape, jnp.float32),
                        (x, dt, Bm, Cm))
    return y


def memory_of(y, z):
    """What the memory's source hands on: the scan's output, ungated."""
    return y


def mamba(u, cfg: Dict[str, Any], lp: Dict[str, Any]):
    """(mix [T, D], memory [T, d_inner])."""
    di, N = lp["A_log"].shape
    r = lp["dt_proj"].shape[0]
    xz = mm(u, f32(lp["in_proj"]))
    x, z = xz[:, :di], xz[:, di:]
    x = jax.nn.silu(conv(x, f32(lp["conv_w"]), f32(lp["conv_b"])))
    dbc = mm(x, f32(lp["x_proj"]))
    dt = jax.nn.softplus(mm(dbc[:, :r], f32(lp["dt_proj"]))
                         + f32(lp["dt_bias"]))
    y = scan(x, dt, -jnp.exp(f32(lp["A_log"])), dbc[:, r:r + N],
             dbc[:, r + N:]) + f32(lp["D"]) * x
    return mm(y * jax.nn.silu(z), f32(lp["out_proj"])), memory_of(y, z)


# ---- S, F, X ----

def causal_mask(T: int, window: Optional[int]):
    rel = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]
    ok = rel >= 0
    return ok & (rel < window) if window else ok


def softmax_attention(q, k, v, mask):
    """q [T, d], k [T, d], v [T, dv]: one head, an explicit softmax."""
    s = mm(q, k.T) / math.sqrt(q.shape[-1])
    s = jnp.where(mask, s, -jnp.inf)
    p = jnp.exp(s - jnp.max(s, -1, keepdims=True))
    return mm(p / jnp.sum(p, -1, keepdims=True), v)


def lambda_init_of(cfg: Dict[str, Any], layer: int) -> float:
    return 0.8 - 0.6 * math.exp(
        -0.3 * (cfg.get("first_layer_index", 0) + layer))


def lambda_of(lp, lam_init: float):
    return (jnp.exp(jnp.sum(f32(lp["lambda_q1"]) * f32(lp["lambda_k1"])))
            - jnp.exp(jnp.sum(f32(lp["lambda_q2"]) * f32(lp["lambda_k2"])))
            + lam_init)


def sub_norm(o, w, eps):
    return o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + eps) * w


def combine(o1, o2, lam):
    return o1 - lam * o2


def differential(q, k, v, cfg, lp, layer: int, window: Optional[int]):
    """q [T, 2P, d], k [T, 2J, d], v [T, J, 2d] -> [T, P * 2d]."""
    T, P, J = q.shape[0], q.shape[1] // 2, k.shape[1] // 2
    mask = causal_mask(T, window)
    lam_init = lambda_init_of(cfg, layer)
    lam = lambda_of(lp, lam_init)
    outs = []
    for p in range(P):
        j = p // (P // J)
        o1 = softmax_attention(q[:, 2 * p], k[:, 2 * j], v[:, j], mask)
        o2 = softmax_attention(q[:, 2 * p + 1], k[:, 2 * j + 1], v[:, j],
                               mask)
        outs.append(sub_norm(combine(o1, o2, lam), f32(lp["subln"]),
                             eps_of(cfg)) * (1.0 - lam_init))
    return jnp.concatenate(outs, -1)


def heads(cfg: Dict[str, Any]):
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return nq, nkv, cfg["hidden_size"] // nq


def keys_values(u, cfg, lp):
    nq, nkv, d = heads(cfg)
    T = u.shape[0]
    k = (mm(u, f32(lp["wk"])) + f32(lp["bk"])).reshape(T, nkv, d)
    v = (mm(u, f32(lp["wv"])) + f32(lp["bv"])).reshape(T, nkv // 2, 2 * d)
    return k, v


def attention(u, cfg, lp, layer: int, window: Optional[int], kv=None):
    """(mix [T, D], (k, v)): self attention, or — ``kv`` given — cross
    attention over another layer's K, V."""
    nq, _, d = heads(cfg)
    q = (mm(u, f32(lp["wq"])) + f32(lp["bq"])).reshape(u.shape[0], nq, d)
    k, v = keys_values(u, cfg, lp) if kv is None else kv
    o = differential(q, k, v, cfg, lp, layer, window)
    return mm(o, f32(lp["wo"])) + f32(lp["bo"]), (k, v)


# ---- G, the MLP, the model ----

def gmu(u, m, lp):
    return mm(m * jax.nn.silu(mm(u, f32(lp["gmu_in"]))), f32(lp["gmu_out"]))


def mlp(a, lp):
    return mm(jax.nn.silu(mm(a, f32(lp["w_gate"]))) * mm(a, f32(lp["w_up"])),
              f32(lp["w_down"]))


def layers_of(params: Dict[str, Any], cfg: Dict[str, Any]):
    """(letter, that layer's leaves) in layer order."""
    seen: Dict[str, int] = {}
    for letter in pattern_of(cfg):
        kind = KINDS[letter]
        j = seen.get(kind, 0)
        seen[kind] = j + 1
        yield letter, {k: w[j] for k, w in params["layers"][kind].items()}


def hidden(params: Dict[str, Any], cfg: Dict[str, Any], tokens):
    """The residual stream behind the last block, [T, D]."""
    pattern, eps = pattern_of(cfg), eps_of(cfg)
    h = f32(params["embedding"])[tokens]
    memories, kvs = {}, {}
    for i, (letter, lp) in enumerate(layers_of(params, cfg)):
        u = layer_norm(h, f32(lp["ln1"]), f32(lp["ln1_b"]), eps)
        if letter == "M":
            mix, memories[i] = mamba(u, cfg, lp)
        elif letter == "G":
            mix = gmu(u, memories[memory_source(pattern)], lp)
        elif letter == "X":
            mix, _ = attention(u, cfg, lp, i, None, kvs[kv_source(pattern)])
        else:
            mix, kvs[i] = attention(
                u, cfg, lp, i, cfg["sliding_window"] if letter == "S" else None)
        h = h + mix
        h = h + mlp(layer_norm(h, f32(lp["ln2"]), f32(lp["ln2_b"]), eps), lp)
    return h


def logits(params: Dict[str, Any], cfg: Dict[str, Any], tokens) -> jnp.ndarray:
    """tokens [T] int -> [T, V] float32, one document."""
    tokens = jnp.asarray(tokens, jnp.int32)
    h = layer_norm(hidden(params, cfg, tokens), f32(params["final_ln"]),
                   f32(params["final_ln_b"]), eps_of(cfg))
    return mm(h, f32(params["embedding"]).T)


def token_logprobs(params, cfg, tokens) -> jnp.ndarray:
    """[T-1]: log p(tokens[t+1] | tokens[:t+1]) for t = 0..T-2."""
    tokens = jnp.asarray(tokens, jnp.int32)
    lp = jax.nn.log_softmax(logits(params, cfg, tokens)[:-1], -1)
    return jnp.take_along_axis(lp, tokens[1:, None], -1)[:, 0]


def loss(params, cfg, tokens) -> jnp.ndarray:
    """Mean negative logprob of one document (for the gradient tests)."""
    return -jnp.mean(token_logprobs(params, cfg, tokens))
