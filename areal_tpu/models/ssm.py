"""The Mamba-2 state-space mixer of a hybrid model's ``mamba`` layers —
packed rows, XLA einsums, no kernel.

One mixer, ``u = norm(h)`` [B, T, D] (models/transformer.py adds the
residual):

    [z | xBC | dt] = u · in_proj          d_inner | d_inner + 2·G·N | H
    xBC = silu(conv1d(xBC))               depthwise, causal, kernel K, bias
    x [H, P], B [G, N], C [G, N] = split(xBC)
    Δ = softplus(dt + dt_bias);  A = -exp(A_log)            (a head each)
    S_t = exp(Δ_t·A) · S_{t-1} + Δ_t · x_t ⊗ B_t            (float32)
    y_t = S_t · C_t + D · x_t             head h reads group h // (H/G)
    y = group_rms_norm(y · silu(z)) · norm_w      G groups of d_inner/G
    out = y · out_proj

**Packed rows.** A row of the grid holds several documents (segment ids,
0 = padding; a document's tokens are contiguous). The state is ZERO before
a document's first token and a convolution tap that would read across a
document's start reads 0 — in the forward and, because both are masks on
what is multiplied, in the backward pass. The resets are exact: a decay
across a boundary is ``exp(-inf) = 0``, never a large negative number
that rounds.

**The scan** is the chunked form (SSD) at ``SSMConfig.chunk_size``: within
a chunk of Q tokens the recurrence is a masked [Q, Q] matmul; each chunk
leaves a state [H, P, N]; the states pass between chunks through one small
matmul over the chunk axis (decays masked where a document ends between
two chunks). The cumulative decays and the states between chunks are
float32; the matmuls take the compute dtype and accumulate in float32.

Device scopes (base/telemetry.SSM_SCOPES): ``ssm_in_proj``, ``ssm_conv``,
``ssm_scan``, ``ssm_gate_norm``, ``ssm_out_proj``. :func:`geometry_counts`
is the trace-time count of the scans a compiled program holds.
"""

from __future__ import annotations

import collections
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from areal_tpu.models.config import SSMConfig

# Scans per compiled program, counted where they are traced (as
# flash_attention.geometry_counts): {(rows, length, chunk, heads, groups):
# calls}.
_GEOMETRY: collections.Counter = collections.Counter()


def geometry_counts() -> Dict[Tuple[int, int, int, int, int], int]:
    return dict(_GEOMETRY)


def init_mamba_params(ssm: SSMConfig, n: int, hidden_dim: int,
                      key: jax.Array, dtype) -> Dict[str, jnp.ndarray]:
    """``n`` stacked Mamba-2 mixers, drawn as the published keys say so
    that the state remembers: Δ0 log-uniform in [time_step_min,
    time_step_max] floored at time_step_floor, ``dt_bias`` its inverse
    softplus; ``A_log = log(U(1, 16))``; ``D = 1``; the convolution as a
    depthwise conv's default (uniform in ±1/sqrt(K)); the two projections
    as the program draws every matrix."""
    k_in, k_conv, k_dt, k_a, k_out = jax.random.split(key, 5)
    H, K = ssm.n_heads, ssm.conv_kernel

    def nrm(k, shape, scale=0.02):
        return (jax.random.normal(k, shape) * scale).astype(dtype)

    dt0 = jnp.exp(
        jax.random.uniform(k_dt, (n, H)) * (
            math.log(ssm.time_step_max) - math.log(ssm.time_step_min))
        + math.log(ssm.time_step_min))
    dt0 = jnp.maximum(dt0, ssm.time_step_floor)
    bound = 1.0 / math.sqrt(K)
    return {
        "ln": jnp.ones((n, hidden_dim), dtype),
        "in_proj": nrm(k_in, (n, hidden_dim, ssm.in_proj_dim)),
        "conv_w": jax.random.uniform(
            k_conv, (n, K, ssm.conv_dim), minval=-bound, maxval=bound
        ).astype(dtype),
        "conv_b": jnp.zeros((n, ssm.conv_dim), dtype),
        "dt_bias": (dt0 + jnp.log(-jnp.expm1(-dt0))).astype(dtype),
        "A_log": jnp.log(jax.random.uniform(
            k_a, (n, H), minval=1.0, maxval=16.0)).astype(dtype),
        "D": jnp.ones((n, H), dtype),
        "norm": jnp.ones((n, ssm.d_inner), dtype),
        "out_proj": nrm(k_out, (n, ssm.d_inner, hidden_dim)),
    }


def causal_conv(x: jnp.ndarray,  # [B, T, C]
                w: jnp.ndarray,  # [K, C]; w[K-1] multiplies the token itself
                b: jnp.ndarray,  # [C]
                seg: jnp.ndarray,  # [B, T]
                ) -> jnp.ndarray:
    """Depthwise causal convolution whose taps stop at a document's
    start: the tap ``j`` tokens back counts only where that token is in
    the same document (and in the row)."""
    K, T = w.shape[0], x.shape[1]
    out = x * w[K - 1] + b
    for j in range(1, K):
        back = jnp.pad(x, ((0, 0), (j, 0), (0, 0)))[:, :T]
        seg_back = jnp.pad(seg, ((0, 0), (j, 0)), constant_values=-1)[:, :T]
        out = out + jnp.where((seg_back == seg)[..., None], back, 0) * w[K - 1 - j]
    return out


def _masked_exp(mask: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """``exp(x)`` where ``mask``, else exactly 0 — in the gradient too."""
    return jnp.exp(jnp.where(mask, x, -jnp.inf))


def ssd_scan(x: jnp.ndarray,  # [B, T, H, P]
             dt: jnp.ndarray,  # [B, T, H] float32, after softplus
             A: jnp.ndarray,  # [H] float32, negative
             Bm: jnp.ndarray,  # [B, T, G, N]
             Cm: jnp.ndarray,  # [B, T, G, N]
             seg: jnp.ndarray,  # [B, T] int; 0 = padding
             chunk: int) -> jnp.ndarray:
    """The recurrence ``S_t = exp(Δ_t A) S_{t-1} + Δ_t x_t ⊗ B_t``,
    ``y_t = S_t C_t`` in chunks of ``chunk`` tokens, ``S`` zero before
    each document's first token. Returns y [B, T, H, P] float32."""
    B_, T, H, P = x.shape
    G, N = Bm.shape[2:]
    Hg = H // G
    Q = chunk
    _GEOMETRY[(B_, T, Q, H, G)] += 1
    pad = -T % Q
    if pad:  # a padded token is its row's padding: Δ = 0 moves nothing
        x, dt, Bm, Cm = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                         for a in (x, dt, Bm, Cm))
        seg = jnp.pad(seg, ((0, 0), (0, pad)))
    Z = (T + pad) // Q
    cd = x.dtype
    f32 = jnp.float32
    seg = seg.reshape(B_, Z, Q)
    # Δ·x in the compute dtype, heads by group: [B, Z, Q, G, Hg, P]
    xdt = (x.astype(f32) * dt[..., None]).astype(cd).reshape(
        B_, Z, Q, G, Hg, P)
    Bm = Bm.reshape(B_, Z, Q, G, N)
    Cm = Cm.reshape(B_, Z, Q, G, N)
    # log-decays, heads before tokens (tokens in the lanes): [B, Z, G, Hg, Q]
    a = jnp.moveaxis((dt * A).reshape(B_, Z, Q, G, Hg), 2, -1)
    cs = jnp.cumsum(a, axis=-1)  # inclusive: through token i of the chunk

    # ---- within a chunk: y_i += sum_{j<=i, same document}
    #      exp(cs_i - cs_j) (C_i·B_j) Δ_j x_j
    same = (seg[:, :, :, None] == seg[:, :, None, :]) & jnp.tril(
        jnp.ones((Q, Q), bool))  # [B, Z, Q(i), Q(j)]
    decay = _masked_exp(same[:, :, None, None],
                        cs[..., :, None] - cs[..., None, :])
    cb = jnp.einsum("bzign,bzjgn->bzgij", Cm, Bm, preferred_element_type=f32)
    m = (decay * cb[:, :, :, None]).astype(cd)  # [B, Z, G, Hg, Q, Q]
    y = jnp.einsum("bzgkij,bzjgkp->bzigkp", m, xdt,
                   preferred_element_type=f32)

    # ---- the state a chunk leaves: sum_j exp(cs_last - cs_j) Δ_j x_j ⊗ B_j
    #      over the tokens of the document its last token is in
    last = seg[:, :, -1]  # [B, Z] document at each chunk's end
    to_end = _masked_exp((seg == last[..., None])[:, :, None, None],
                         cs[..., -1:] - cs)  # [B, Z, G, Hg, Q]
    states = jnp.einsum(
        "bzjgn,bzjgkp->bzgkpn", Bm,
        (xdt * jnp.moveaxis(to_end, -1, 2)[..., None]).astype(cd),
        preferred_element_type=f32)  # [B, Z, G, Hg, P, N] float32

    # ---- between chunks: the state entering chunk z is the sum over the
    #      chunks c < z that end in the document chunk z-1 ends in (then
    #      every token between is in it), decayed by the chunks between
    total = jnp.cumsum(cs[..., -1], axis=1)  # [B, Z, G, Hg] through chunk z
    total_prev = jnp.pad(total, ((0, 0), (1, 0), (0, 0), (0, 0)))[:, :Z]
    prev = jnp.pad(last, ((0, 0), (1, 0)), constant_values=-1)[:, :Z]
    carries = (prev[:, :, None] == last[:, None, :]) & jnp.tril(
        jnp.ones((Z, Z), bool), -1)  # [B, Z(z), Z(c)]
    w = _masked_exp(carries[..., None, None],
                    total_prev[:, :, None] - total[:, None, :])
    entering = jnp.einsum("bzcgk,bcgkpn->bzgkpn", w, states,
                          precision=jax.lax.Precision.HIGHEST)
    # ---- what the entering state adds inside chunk z: C_i · S exp(cs_i),
    #      for the tokens still in that document
    from_start = _masked_exp((seg == prev[..., None])[:, :, None, None], cs)
    y = y + jnp.einsum(
        "bzign,bzgkpn->bzigkp", Cm, entering.astype(cd),
        preferred_element_type=f32) * jnp.moveaxis(from_start, -1, 2)[..., None]
    return y.reshape(B_, Z * Q, H, P)[:, :T]


def group_rms_norm(y: jnp.ndarray,  # [..., d_inner] float32
                   w: jnp.ndarray, groups: int, eps: float) -> jnp.ndarray:
    """RMSNorm over each of ``groups`` groups of channels, then the
    weight."""
    shape = y.shape
    y = y.reshape(shape[:-1] + (groups, shape[-1] // groups))
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    return y.reshape(shape) * w


def mamba_mixer(u: jnp.ndarray,  # [B, T, D] the normed residual stream
                lp: Dict[str, jnp.ndarray],  # this layer's parameters
                ssm: SSMConfig, eps: float,
                segment_ids: Optional[jnp.ndarray],  # [B, T]; None = one document a row
                ) -> jnp.ndarray:
    B_, T, _ = u.shape
    H, P, G, N = ssm.n_heads, ssm.head_dim, ssm.n_groups, ssm.state_dim
    di = ssm.d_inner
    seg = (jnp.ones((B_, T), jnp.int32) if segment_ids is None
           else segment_ids)
    with jax.named_scope("ssm_in_proj"):
        zxbcdt = u @ lp["in_proj"]
        z, xBC, dt = jnp.split(zxbcdt, [di, di + ssm.conv_dim], axis=-1)
    with jax.named_scope("ssm_conv"):
        xBC = jax.nn.silu(causal_conv(xBC, lp["conv_w"], lp["conv_b"], seg))
        x, Bm, Cm = jnp.split(xBC, [di, di + G * N], axis=-1)
        x = x.reshape(B_, T, H, P)
    with jax.named_scope("ssm_scan"):
        f32 = jnp.float32
        dt = jax.nn.softplus(dt.astype(f32) + lp["dt_bias"].astype(f32))
        A = -jnp.exp(lp["A_log"].astype(f32))
        y = ssd_scan(x, dt, A, Bm.reshape(B_, T, G, N),
                     Cm.reshape(B_, T, G, N), seg, ssm.chunk_size)
        y = y + lp["D"].astype(f32)[:, None] * x.astype(f32)
    with jax.named_scope("ssm_gate_norm"):
        y = y.reshape(B_, T, di) * jax.nn.silu(z.astype(f32))
        y = group_rms_norm(y, lp["norm"].astype(f32), G, eps).astype(u.dtype)
    with jax.named_scope("ssm_out_proj"):
        return y @ lp["out_proj"]
