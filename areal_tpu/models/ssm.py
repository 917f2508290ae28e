"""The state-space mixers: Mamba-2 (SSD) of a hybrid model's ``mamba``
layers (a mixer alone: nemotron_h) and ``ssd`` blocks (the mixer, then a
dense MLP: granitemoehybrid) — packed rows; the scan a Pallas kernel on a
TPU (ops/pallas/ssd_scan.py), XLA einsums elsewhere — and, at the end of
the file, Mamba-1's selective scan (S6) of the ``s6`` blocks.

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
On a TPU the same algorithm runs as one kernel a pass (:func:`ssd_scan`,
``impl``): the [Q, Q] blocks stay in VMEM and the state rides the chunk
axis; :func:`scan_impl_counts` says which form each traced scan took.

Device scopes (base/telemetry.SSM_SCOPES): ``ssm_in_proj``, ``ssm_conv``,
``ssm_scan``, ``ssm_gate_norm``, ``ssm_out_proj``. :func:`geometry_counts`
is the trace-time count of the scans a compiled program holds.
"""

from __future__ import annotations

import collections
import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from areal_tpu.models.config import S6Config, SSMConfig

# Scans per compiled program, counted where they are traced (as
# window_attention.geometry_counts): {(rows, length, chunk, heads, groups):
# calls}.
_GEOMETRY: collections.Counter = collections.Counter()


# The same scans by what runs them: {"pallas" | "pallas_interpret" | "xla":
# calls} (as attention.dispatch_counts).
_SCAN_IMPL: collections.Counter = collections.Counter()


def geometry_counts() -> Dict[Tuple[int, int, int, int, int], int]:
    return dict(_GEOMETRY)


def scan_impl_counts() -> Dict[str, int]:
    return dict(_SCAN_IMPL)


def ssd_kernel_frac() -> Optional[float]:
    """The share of the traced Mamba-2 scans that run the kernel; None
    where none was traced."""
    total = sum(_SCAN_IMPL.values())
    return (total - _SCAN_IMPL["xla"]) / total if total else None


def init_mamba_params(ssm: SSMConfig, n: int, hidden_dim: int,
                      key: jax.Array, dtype) -> Dict[str, jnp.ndarray]:
    """``n`` stacked Mamba-2 mixers (the norm in front is the layer's or
    the block's own), drawn as the published keys say so
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
             chunk: int, impl: str = "auto") -> jnp.ndarray:
    """The recurrence ``S_t = exp(Δ_t A) S_{t-1} + Δ_t x_t ⊗ B_t``,
    ``y_t = S_t C_t`` in chunks of ``chunk`` tokens, ``S`` zero before
    each document's first token. Returns y [B, T, H, P] float32. ``impl``
    as :func:`selective_scan`'s: the kernel on a TPU for the widths it
    takes, the einsums below elsewhere."""
    B_, T, H, P = x.shape
    G, N = Bm.shape[2:]
    Hg = H // G
    Q = chunk
    how = _ssd_impl(impl, Q, H, P, G, N)
    _GEOMETRY[(B_, T, Q, H, G)] += 1
    _SCAN_IMPL[how] += 1
    if how != "xla":  # the kernel takes a row of any length
        return _ssd_kernel(x, dt, dt * A, Bm, Cm, seg, Q, how)
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


def _ssd_impl(impl: str, chunk: int, H: int, P: int, G: int, N: int) -> str:
    """"pallas" | "pallas_interpret" | "xla", as :func:`_scan_impl`."""
    from areal_tpu.ops.attention import _wants_kernel
    from areal_tpu.ops.pallas import ssd_scan as kernel

    if not kernel.supported(chunk, H, P, G, N):
        return "xla"
    if impl == "pallas_interpret":
        return impl
    # a chip without the VMEM the kernel asks for: the einsums, counted
    return "pallas" if _wants_kernel(impl) and kernel.fits_device() else "xla"


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _ssd_kernel(x, dt, a, Bm, Cm, seg, chunk, how):
    """The kernel's scan of x [B, T, H, P] under Δ and the log-decays ``a =
    Δ·A`` [B, T, H] float32; T any length."""
    from areal_tpu.ops.pallas import ssd_scan as kernel

    with jax.named_scope("ssm_scan"):
        return kernel.scan_fwd(x, dt, a, Bm, Cm, seg, chunk,
                               interpret=how == "pallas_interpret")[0]


def _ssd_kernel_fwd(x, dt, a, Bm, Cm, seg, chunk, how):
    from areal_tpu.ops.pallas import ssd_scan as kernel

    with jax.named_scope("ssm_scan"):
        y, states = kernel.scan_fwd(x, dt, a, Bm, Cm, seg, chunk, keep=True,
                                    interpret=how == "pallas_interpret")
    return y, (x, dt, a, Bm, Cm, seg, states, y)


def _ssd_kernel_bwd(chunk, how, res, dy):
    from areal_tpu.ops.pallas import ssd_scan as kernel

    x, dt, a, Bm, Cm, seg, states, y = res
    with jax.named_scope("ssm_scan"):
        dx, ddt, da, dB, dC = kernel.scan_bwd(
            x, dt, a, Bm, Cm, seg, states, y, dy, chunk,
            interpret=how == "pallas_interpret")
    return dx, ddt, da, dB.astype(Bm.dtype), dC.astype(Cm.dtype), None


_ssd_kernel.defvjp(_ssd_kernel_fwd, _ssd_kernel_bwd)


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
                impl: str = "auto",
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
    with jax.named_scope("ssm_scan"):
        f32 = jnp.float32
        dt = jax.nn.softplus(dt.astype(f32) + lp["dt_bias"].astype(f32))
        A = -jnp.exp(lp["A_log"].astype(f32))
        y = ssd_scan(x.reshape(B_, T, H, P), dt, A, Bm.reshape(B_, T, G, N),
                     Cm.reshape(B_, T, G, N), seg, ssm.chunk_size, impl)
        # D · x a head, on the [B, T, d_inner] rows the kernel reads and
        # writes: a [.., H, 64] array in between would be a second layout
        y = y.reshape(B_, T, di) + jnp.repeat(
            lp["D"].astype(f32), P) * x.astype(f32)
    with jax.named_scope("ssm_gate_norm"):
        y = y * jax.nn.silu(z.astype(f32))
        y = group_rms_norm(y, lp["norm"].astype(f32), G, eps).astype(u.dtype)
    with jax.named_scope("ssm_out_proj"):
        return y @ lp["out_proj"]


# ---------------- Mamba-1: the selective scan (S6) ----------------
#
# One mixer, ``u = norm(h)`` [B, T, D] (the block adds the residual and
# the MLP, models/transformer.py):
#
#     [x | z] = u · in_proj                       d_inner | d_inner
#     x = silu(conv1d(x) + b)                     depthwise, causal, K taps
#     [δ | B | C] = x · x_proj                    dt_rank | N | N
#     Δ = softplus(δ · dt_proj + dt_bias)         [d_inner]
#     A = -exp(A_log)                             [d_inner, N]
#     h_t = exp(Δ_t ⊙ A) ⊙ h_{t-1} + (Δ_t ⊙ x_t) ⊗ B_t        (float32)
#     y_t = h_t · C_t + D ⊙ x_t
#     out = (y ⊙ silu(z)) · out_proj
#
# ``y`` (before the gate) is the MEMORY a later gated memory unit reads:
# :func:`s6_mixer` returns it beside ``out``. The state is zero before a
# document's first token and the convolution's taps stop there, as above.
#
# The scan is NOT the chunked matmul form: the decay is a channel's and a
# state's own, so it runs as a recurrence, in chunks whose entering states
# are all the backward pass keeps (:func:`selective_scan`): a Pallas
# kernel on a TPU (ops/pallas/selective_scan.py), the same algorithm as
# two ``lax.scan`` elsewhere. Device scopes (base/telemetry.SAMBAY_SCOPES):
# ``s6_in_proj``, ``s6_conv``, ``s6_xdt_proj``, ``s6_scan``, ``s6_out_proj``.

# Scans per compiled program: {(rows, length, d_inner, state, impl): calls}.
_S6_GEOMETRY: collections.Counter = collections.Counter()
# Tokens between two kept states of the XLA form (the kernel has its own).
S6_CHUNK = 64


def s6_geometry_counts() -> Dict[Tuple[int, int, int, int, str], int]:
    return dict(_S6_GEOMETRY)


def init_s6_params(s6: S6Config, n: int, hidden_dim: int, key: jax.Array,
                   dtype) -> Dict[str, jnp.ndarray]:
    """``n`` stacked S6 mixers, drawn as Mamba-1 draws them: Δ's bias the
    inverse softplus of logUniform(time_step_min, time_step_max) floored
    at time_step_floor; ``dt_proj`` uniform in ±dt_rank^-1/2; ``A_log =
    log(1..N)`` on every channel; ``D = 1``; the convolution uniform in
    ±1/sqrt(K); every other matrix as the program draws matrices."""
    k_in, k_conv, k_x, k_dtw, k_dt, k_out = jax.random.split(key, 6)
    di, N, K, r = s6.d_inner, s6.state_dim, s6.conv_kernel, s6.dt_rank

    def nrm(k, shape, scale=0.02):
        return (jax.random.normal(k, shape) * scale).astype(dtype)

    dt0 = jnp.exp(
        jax.random.uniform(k_dt, (n, di)) * (
            math.log(s6.time_step_max) - math.log(s6.time_step_min))
        + math.log(s6.time_step_min))
    dt0 = jnp.maximum(dt0, s6.time_step_floor)
    bound, dt_bound = 1.0 / math.sqrt(K), r ** -0.5
    return {
        "in_proj": nrm(k_in, (n, hidden_dim, 2 * di)),
        "conv_w": jax.random.uniform(
            k_conv, (n, K, di), minval=-bound, maxval=bound).astype(dtype),
        "conv_b": jnp.zeros((n, di), dtype),
        "x_proj": nrm(k_x, (n, di, s6.x_proj_dim)),
        "dt_proj": jax.random.uniform(
            k_dtw, (n, r, di), minval=-dt_bound, maxval=dt_bound
        ).astype(dtype),
        "dt_bias": (dt0 + jnp.log(-jnp.expm1(-dt0))).astype(dtype),
        "A_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)), (n, di, N)
        ).astype(dtype),
        "D": jnp.ones((n, di), dtype),
        "out_proj": nrm(k_out, (n, di, hidden_dim)),
    }


def document_starts(seg: jnp.ndarray) -> jnp.ndarray:
    """[B, T] int32: 0 at a document's first token (and on padding), where
    the state before it is zero; 1 elsewhere."""
    prev = jnp.pad(seg, ((0, 0), (1, 0)), constant_values=-1)[:, :-1]
    return ((seg == prev) & (seg > 0)).astype(jnp.int32)


def _chunks(a: jnp.ndarray, Q: int) -> jnp.ndarray:
    """[B, T, ...] -> [T / Q, Q, B, ...]: chunks, then tokens, lead."""
    B_, T = a.shape[:2]
    return jnp.moveaxis(a.reshape(B_, T // Q, Q, *a.shape[2:]), 0, 2)


def _unchunk(a: jnp.ndarray) -> jnp.ndarray:
    Z, Q, B_ = a.shape[:3]
    return jnp.moveaxis(a, 2, 0).reshape(B_, Z * Q, *a.shape[3:])


def _xla_scan_fwd(x, dt, A, Bm, Cm, keep):
    """The kernel's forward as two nested ``lax.scan``: over chunks (whose
    entering states are kept) and over a chunk's tokens."""
    B_, T, D = x.shape
    Q = S6_CHUNK

    def token(h, xs):  # h [B, D, N]
        x_t, dt_t, b_t, c_t, k_t = xs
        a = jnp.exp(dt_t[..., None] * A) * k_t[:, None, None]
        h = a * h + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return h, jnp.einsum("bdn,bn->bd", h, c_t)

    def chunk(h, xs):
        h_out, y = jax.lax.scan(token, h, xs)
        return h_out, (y, h)

    xs = tuple(_chunks(a, Q) for a in (x, dt, Bm, Cm, keep))
    _, (y, h0) = jax.lax.scan(
        chunk, jnp.zeros((B_, D, A.shape[1]), jnp.float32), xs)
    return _unchunk(y), h0  # h0 [Z, B, D, N]


def _xla_scan_bwd(x, dt, A, Bm, Cm, keep, h0, dy):
    B_, T, D = x.shape
    Q = S6_CHUNK

    def decay(dt_t, k_t):
        return jnp.exp(dt_t[..., None] * A) * k_t[:, None, None]

    def token(h, xs):  # the states again: ys = the state BEFORE a token
        x_t, dt_t, b_t, k_t = xs
        h2 = decay(dt_t, k_t) * h + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return h2, (h, h2)

    def back(carry, xs):
        g, dA = carry
        x_t, dt_t, b_t, c_t, k_t, dy_t, h_prev, h_t = xs
        a = decay(dt_t, k_t)
        g = g + dy_t[..., None] * c_t[:, None, :]
        dc = jnp.einsum("bdn,bd->bn", h_t, dy_t)
        db = jnp.einsum("bdn,bd->bn", g, dt_t * x_t)
        s = jnp.einsum("bdn,bn->bd", g, b_t)
        e = g * h_prev * a
        ddt = s * x_t + jnp.sum(e * A, axis=-1)
        dA = dA + jnp.sum(e * dt_t[..., None], axis=0)
        return (a * g, dA), (s * dt_t, ddt, db, dc)

    def chunk(carry, xs):
        x_c, dt_c, b_c, c_c, k_c, dy_c, h_in = xs
        _, (h_prev, h_t) = jax.lax.scan(token, h_in, (x_c, dt_c, b_c, k_c))
        return jax.lax.scan(
            back, carry, (x_c, dt_c, b_c, c_c, k_c, dy_c, h_prev, h_t),
            reverse=True)

    xs = tuple(_chunks(a, Q) for a in (x, dt, Bm, Cm, keep, dy)) + (h0,)
    zero = jnp.zeros((B_, D, A.shape[1]), jnp.float32)
    (_, dA), grads = jax.lax.scan(chunk, (zero, jnp.zeros_like(A)), xs,
                                  reverse=True)
    dx, ddt, db, dc = (_unchunk(a) for a in grads)
    return dx, ddt, dA, db, dc


def _scan_impl(impl: str, D: int) -> str:
    """"pallas" | "pallas_interpret" | "xla": the kernel on a TPU (or
    where it is asked for), for the widths it takes; else the XLA form."""
    from areal_tpu.ops.attention import _wants_kernel
    from areal_tpu.ops.pallas import selective_scan as kernel

    if impl == "pallas_interpret":
        return impl
    return "pallas" if _wants_kernel(impl) and kernel.supported(D) else "xla"


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _selective_scan(x, dt, A, Bm, Cm, keep, how):
    return _selective_scan_fwd(x, dt, A, Bm, Cm, keep, how)[0]


def _selective_scan_fwd(x, dt, A, Bm, Cm, keep, how):
    if how == "xla":
        y, h0 = _xla_scan_fwd(x, dt, A, Bm, Cm, keep.astype(jnp.float32))
    else:
        from areal_tpu.ops.pallas import selective_scan as kernel

        y, h0 = kernel.scan_fwd(x, dt, A, Bm, Cm, keep,
                                interpret=how == "pallas_interpret")
    return y, (x, dt, A, Bm, Cm, keep, h0)


def _selective_scan_bwd(how, res, dy):
    x, dt, A, Bm, Cm, keep, h0 = res
    if how == "xla":
        grads = _xla_scan_bwd(x, dt, A, Bm, Cm, keep.astype(jnp.float32),
                              h0, dy)
    else:
        from areal_tpu.ops.pallas import selective_scan as kernel

        grads = kernel.scan_bwd(x, dt, A, Bm, Cm, keep, h0, dy,
                                interpret=how == "pallas_interpret")
    return (*grads, None)


_selective_scan.defvjp(_selective_scan_fwd, _selective_scan_bwd)


def selective_scan(x: jnp.ndarray,  # [B, T, D]
                   dt: jnp.ndarray,  # [B, T, D], after softplus
                   A: jnp.ndarray,  # [D, N], negative
                   Bm: jnp.ndarray,  # [B, T, N]
                   Cm: jnp.ndarray,  # [B, T, N]
                   D_skip: jnp.ndarray,  # [D]
                   seg: jnp.ndarray,  # [B, T] int; 0 = padding
                   impl: str = "auto") -> jnp.ndarray:
    """``h_t = exp(Δ_t ⊙ A) ⊙ h_{t-1} + (Δ_t ⊙ x_t) ⊗ B_t``, ``y_t = h_t ·
    C_t + D ⊙ x_t``, ``h`` zero before each document's first token, in
    float32. Returns y [B, T, D] float32. Only the state entering every
    chunk outlives the forward pass: the backward pass (a custom one)
    re-runs a chunk's recurrence from it, so no [T, D, N] array exists in
    either pass."""
    f32 = jnp.float32
    B_, T, D = x.shape
    how = _scan_impl(impl, D)
    _S6_GEOMETRY[(B_, T, D, A.shape[1], how)] += 1
    x, dt, Bm, Cm = (a.astype(f32) for a in (x, dt, Bm, Cm))
    keep = document_starts(seg)
    pad = -T % S6_CHUNK
    if pad:  # a padded token is its row's padding: it resets and adds 0
        x, dt, Bm, Cm = (jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
                         for a in (x, dt, Bm, Cm))
        keep = jnp.pad(keep, ((0, 0), (0, pad)))
    y = _selective_scan(x, dt, A.astype(f32), Bm, Cm, keep, how)[:, :T]
    return y + D_skip.astype(f32) * x[:, :T]


def s6_mixer(u: jnp.ndarray,  # [B, T, D] the normed residual stream
             lp: Dict[str, jnp.ndarray],  # this layer's parameters
             s6: S6Config,
             segment_ids: Optional[jnp.ndarray],  # [B, T]; None = one document a row
             impl: str = "auto",
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(the mixer's output [B, T, D], the scan's output ``y`` before the
    gate [B, T, d_inner] — the memory a gated memory unit reads)."""
    B_, T, _ = u.shape
    di, N, r = s6.d_inner, s6.state_dim, s6.dt_rank
    f32 = jnp.float32
    seg = (jnp.ones((B_, T), jnp.int32) if segment_ids is None
           else segment_ids)
    with jax.named_scope("s6_in_proj"):
        x, z = jnp.split(u @ lp["in_proj"], [di], axis=-1)
    with jax.named_scope("s6_conv"):
        x = jax.nn.silu(causal_conv(x, lp["conv_w"], lp["conv_b"], seg))
    with jax.named_scope("s6_xdt_proj"):
        delta, Bm, Cm = jnp.split(x @ lp["x_proj"], [r, r + N], axis=-1)
        dt = jax.nn.softplus(
            (delta @ lp["dt_proj"]).astype(f32) + lp["dt_bias"].astype(f32))
    with jax.named_scope("s6_scan"):
        y = selective_scan(
            x, dt, -jnp.exp(lp["A_log"].astype(f32)), Bm, Cm, lp["D"], seg,
            impl).astype(u.dtype)
    with jax.named_scope("s6_out_proj"):
        return (y * jax.nn.silu(z)) @ lp["out_proj"], y


def gated_memory_unit(u: jnp.ndarray,  # [B, T, D] the normed residual stream
                      memory: jnp.ndarray,  # [B, T, d_inner], an S6 layer's y
                      lp: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    """``(m ⊙ silu(u W_1)) W_2``: nothing across tokens."""
    with jax.named_scope("gmu"):
        return (memory * jax.nn.silu(u @ lp["gmu_in"])) @ lp["gmu_out"]
