"""The Gated DeltaNet linear-attention mixer of a ``gdn`` block
(qwen3_next's ``linear_attention`` layers) — packed rows, the gated delta
rule in chunks: on a TPU, at the published head sizes, the Pallas kernel
pair of ``ops/pallas/gated_delta_rule.py``, which then does the mixer's two
per-head norms too (:func:`_rule_impl` chooses, by what it can see;
:func:`rule_impl_counts` and :func:`mixer_norm_counts` say what it chose);
elsewhere XLA matmuls and one ``lax.scan`` over the chunks.

One mixer, ``u = norm(h)`` [B, T, D] (models/transformer.py adds the
residual and the block's FFN); ``G`` key heads of ``dk``, ``H = r·G``
value heads of ``dv``, value head ``i`` reads key head ``i // r``:

    [q | k | v | z] = u · gdn_qkvz            G·dk | G·dk | H·dv | H·dv
    [b | a] = u · gdn_ba                      H | H
    [q | k | v] = silu(conv1d([q | k | v]))   depthwise, causal, K taps, no bias
    β = sigmoid(b);  g = -exp(A_log) · softplus(a + dt_bias)    (a value head)
    q̂ = q · rsqrt(Σ q² + 1e-6) · dk^-1/2;  k̂ = k · rsqrt(Σ k² + 1e-6)
    S ← e^{g_t} S;  δ_t = β_t (v_t − Sᵀ k̂_t);  S ← S + k̂_t δ_tᵀ;  o_t = Sᵀ q̂_t
    y = rms(o) · gdn_norm ⊙ silu(z)           over each head's dv channels
    out = y · gdn_out

The projections are laid out PLAINLY here — all of q, then k, v, z; all of
b, then a — and models/hf.py turns them into the publisher's layout by key
head and back.

**Packed rows**, as models/ssm.py: the state is ZERO before a document's
first token and a convolution tap that would read across a document's
start reads 0; both are masks on what is multiplied, so they hold in the
backward pass, and every exponent is that of a NON-POSITIVE difference of
cumulated ``g`` (``ssm._masked_exp``: exactly 0 where masked).

**The rule in chunks** of ``GDNConfig.chunk_size`` tokens
(:func:`gated_delta_rule`). With ``c`` the cumulated ``g`` inside a chunk
and ``S₀`` the state entering it, the chunk's ``δ`` solve ``(I + A) Δ =
β ⊙ (V − e^c ⊙ K̂ S₀)`` where ``A_ij = β_i e^{c_i − c_j} k̂_i·k̂_j`` for ``j
< i`` in the same document — a unit lower-triangular system a chunk a
value head. In the XLA form ``(I + A)^-1`` is a product of ``log2 chunk``
matrices (:func:`_unit_lower_inverse`: A is nilpotent; the kernels invert
by blocks, which also holds where a chunk's keys resemble each other and
the powers of ``A`` grow), applied once to ``β V`` and once to ``β e^c K̂``
(the WY / UT transform); the states are then carried chunk to chunk by a
``lax.scan`` whose step is five small matmuls a head.
A document start inside a chunk masks the chunk's two triangular
matrices, which tokens read the entering state, and what of the chunk the
state it leaves holds. ``A``, its inverse, the decays and the carried
state are float32; the matmuls take the compute dtype and sum in float32.

Device scopes (base/telemetry.GDN_SCOPES): ``gdn_in_proj``, ``gdn_conv``,
``gdn_gates``, ``gdn_rule``, ``gdn_gate_norm``, ``gdn_out_proj``.
:func:`geometry_counts` is the trace-time count of the rules a compiled
program holds.
"""

from __future__ import annotations

import collections
import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from areal_tpu.models.config import GDNConfig
from areal_tpu.models.ssm import _masked_exp, causal_conv

# Why a model with these blocks is not decoded here (models/generate.py
# and transformer.forward refuse it by this name).
DECODE_REFUSAL = (
    "delta_rule_decode_state: a Gated DeltaNet block decodes from a "
    "recurrent matrix a value head (S [dk, dv]) and its convolution's last "
    "taps, which no cache here holds")

# Rules per compiled program, counted where they are traced (as
# ssm.geometry_counts): {(rows, length, chunk, key heads, value heads, dk,
# dv): calls}.
_GEOMETRY: collections.Counter = collections.Counter()

L2_EPS = 1e-6  # inside the rsqrt of q's and k's l2 norm (fla's, HF's)


def geometry_counts() -> Dict[Tuple[int, int, int, int, int, int, int], int]:
    return dict(_GEOMETRY)


# Which form each traced rule took (:func:`_rule_impl`): "pallas" |
# "pallas_interpret" | "xla".
_RULE_IMPL: collections.Counter = collections.Counter()


def rule_impl_counts() -> Dict[str, int]:
    return dict(_RULE_IMPL)


def rule_kernel_frac() -> Optional[float]:
    """Of the rules traced so far, the share that took the Pallas kernels;
    None before the first trace."""
    total = sum(_RULE_IMPL.values())
    return (total - _RULE_IMPL["xla"]) / total if total else None


# Where each traced mixer's two norms ran (:func:`gdn_mixer`): "kernel" —
# inside the rule's kernels (:func:`rule_with_norms`) — or "xla".
_MIXER_NORMS: collections.Counter = collections.Counter()


def mixer_norm_counts() -> Dict[str, int]:
    return dict(_MIXER_NORMS)


def norms_in_kernel_frac() -> Optional[float]:
    """Of the mixers traced so far, the share whose l2 norms and gated RMS
    norm ran inside the rule's kernels; None before the first trace."""
    total = sum(_MIXER_NORMS.values())
    return _MIXER_NORMS["kernel"] / total if total else None


def init_gdn_params(gdn: GDNConfig, n: int, hidden_dim: int, key: jax.Array,
                    dtype) -> Dict[str, jnp.ndarray]:
    """``n`` stacked mixers (the norm in front is the block's own):
    ``A_log = log U(1, 16)`` (HF draws U(0, 16): the floor keeps the log
    finite), ``dt_bias`` 1, the convolution U(±1/2) (a depthwise conv's
    default at 4 taps), the gated norm's weight 1, every matrix as the
    program draws matrices."""
    k_in, k_ba, k_conv, k_a, k_out = jax.random.split(key, 5)
    H, K = gdn.n_v_heads, gdn.conv_kernel

    def nrm(k, shape, scale=0.02):
        return (jax.random.normal(k, shape) * scale).astype(dtype)

    bound = K ** -0.5
    return {
        "gdn_qkvz": nrm(k_in, (n, hidden_dim, gdn.qkvz_dim)),
        "gdn_ba": nrm(k_ba, (n, hidden_dim, gdn.ba_dim)),
        "gdn_conv": jax.random.uniform(
            k_conv, (n, K, gdn.conv_dim), minval=-bound, maxval=bound
        ).astype(dtype),
        "gdn_dt_bias": jnp.ones((n, H), dtype),
        "gdn_A_log": jnp.log(jax.random.uniform(
            k_a, (n, H), minval=1.0, maxval=16.0)).astype(dtype),
        "gdn_norm": jnp.ones((n, gdn.v_head_dim), dtype),
        "gdn_out": nrm(k_out, (n, gdn.value_dim, hidden_dim)),
    }


def gdn_param_count(gdn: GDNConfig, hidden_dim: int) -> int:
    """Parameters of one mixer, the norm in front not counted."""
    return (hidden_dim * (gdn.qkvz_dim + gdn.ba_dim)
            + gdn.conv_kernel * gdn.conv_dim + 2 * gdn.n_v_heads
            + gdn.v_head_dim + gdn.value_dim * hidden_dim)


def l2_normalize(x: jnp.ndarray) -> jnp.ndarray:
    """``x · rsqrt(Σ x² + 1e-6)`` over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


_HIGHEST = jax.lax.Precision.HIGHEST


@jax.custom_vjp
def _unit_lower_inverse(A: jnp.ndarray) -> jnp.ndarray:
    """``(I + A)^-1`` for ``A`` [..., Q, Q] float32 strictly lower
    triangular: ``A^Q = 0``, so the inverse is the finite sum of
    ``(-A)^k``, which is ``(I + X)(I + X²)(I + X⁴)…`` with ``X = -A`` —
    ``log2 Q`` squarings. Its cotangent is ``-Mᵀ M̄ Mᵀ``: nothing of the
    product's factors is kept."""
    Q = A.shape[-1]
    X = -A
    M = jnp.eye(Q, dtype=A.dtype) + X
    n = 2  # powers of X below n are in M
    while n < Q:
        X = jnp.matmul(X, X, precision=_HIGHEST)
        M = M + jnp.matmul(M, X, precision=_HIGHEST)
        n *= 2
    return M


def _unit_lower_inverse_fwd(A):
    M = _unit_lower_inverse(A)
    return M, M


def _unit_lower_inverse_bwd(M, ct):
    Mt = jnp.swapaxes(M, -1, -2)
    return (-jnp.matmul(jnp.matmul(Mt, ct, precision=_HIGHEST), Mt,
                        precision=_HIGHEST),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def gated_delta_rule(q: jnp.ndarray,  # [B, T, G, dk], l2-normed and scaled
                     k: jnp.ndarray,  # [B, T, G, dk], l2-normed
                     v: jnp.ndarray,  # [B, T, H, dv]
                     g: jnp.ndarray,  # [B, T, H] float32 log-decay, <= 0
                     beta: jnp.ndarray,  # [B, T, H] float32 in (0, 1)
                     seg: jnp.ndarray,  # [B, T] int; 0 = padding
                     chunk: int, impl: str = "auto") -> jnp.ndarray:
    """The gated delta rule of the module's docstring in chunks of
    ``chunk`` tokens, ``S`` zero before each document's first token.
    Returns o [B, T, H, dv] float32. ``impl`` as ``attn_impl``: on a TPU
    the Pallas kernel pair where :func:`_rule_impl` finds it can run."""
    B_, T, G, dk = q.shape
    H, dv = v.shape[2:]
    r = H // G
    Q = chunk
    how = _rule_impl(impl, Q, G, H, dk, dv, v.dtype)
    _RULE_IMPL[how] += 1
    pad = -T % Q
    if pad:  # a padded token is its row's padding: beta = 0 writes nothing
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
        seg = jnp.pad(seg, ((0, 0), (0, pad)))
    Z = (T + pad) // Q
    cd, f32 = v.dtype, jnp.float32
    if how != "xla":
        return _rule_kernel(q.astype(cd), k.astype(cd), v, g.astype(f32),
                            beta.astype(f32), seg, Q, how)[:, :T]
    seg = seg.reshape(B_, Z, Q)
    # heads before tokens, so that every array's two minor dims are a
    # chunk's tokens and a head's channels (or tokens and tokens):
    # q, k [B, Z, G, Q, dk]; v [B, Z, G, r, Q, dv]; g, beta [B, Z, G, r, Q]
    q = jnp.moveaxis(q.astype(cd).reshape(B_, Z, Q, G, dk), 2, 3)
    k = jnp.moveaxis(k.astype(cd).reshape(B_, Z, Q, G, dk), 2, 3)
    v = jnp.moveaxis(v.reshape(B_, Z, Q, G, r, dv), 2, 4)
    g = jnp.moveaxis(g.astype(f32).reshape(B_, Z, Q, G, r), 2, -1)
    beta = jnp.moveaxis(beta.astype(f32).reshape(B_, Z, Q, G, r), 2, -1)
    c = jnp.cumsum(g, axis=-1)  # inclusive: through token i of the chunk

    # ---- inside a chunk: the two triangular matrices, masked to the
    #      pairs of one document
    same = seg[:, :, :, None] == seg[:, :, None, :]  # [B, Z, Q(i), Q(j)]
    tri = jnp.tril(jnp.ones((Q, Q), bool))
    diff = c[..., :, None] - c[..., None, :]  # [B, Z, G, r, Q, Q]
    below = _masked_exp((same & jnp.tril(tri, -1))[:, :, None, None], diff)
    upto = _masked_exp((same & tri)[:, :, None, None], diff)
    kk = jnp.einsum("bzgik,bzgjk->bzgij", k, k, preferred_element_type=f32)
    qk = jnp.einsum("bzgik,bzgjk->bzgij", q, k, preferred_element_type=f32)
    A = beta[..., :, None] * kk[:, :, :, None] * below
    M = _unit_lower_inverse(A).astype(cd)
    P = (qk[:, :, :, None] * upto).astype(cd)

    # ---- what reads the entering state, and what the chunk leaves: the
    #      tokens of the document the chunk before ended in read S₀; the
    #      state left is that of the document the chunk's last token is in
    last = seg[:, :, -1]  # [B, Z]
    prev = jnp.pad(last, ((0, 0), (1, 0)), constant_values=-1)[:, :Z]
    enters = _masked_exp((seg == prev[..., None])[:, :, None, None], c)
    to_end = _masked_exp((seg == last[..., None])[:, :, None, None],
                         c[..., -1:] - c)
    keeps = _masked_exp((last == prev)[:, :, None, None], c[..., -1])
    # a key head's q and k for each of its value heads: [B, Z, G, 1, Q, dk]
    qv, kv = (a[:, :, :, None].astype(f32) for a in (q, k))
    U = jnp.einsum("bzgrij,bzgrjv->bzgriv", M,
                   (v.astype(f32) * beta[..., None]).astype(cd),
                   preferred_element_type=f32)
    W = jnp.einsum("bzgrij,bzgrjk->bzgrik", M,
                   (kv * (beta * enters)[..., None]).astype(cd),
                   preferred_element_type=f32).astype(cd)
    qe = (qv * enters[..., None]).astype(cd)
    kd = (kv * to_end[..., None]).astype(cd)

    return _carry_states(U, W, qe, kd, P, keeps, T)


def _carry_states(U, W, qe, kd, P, keeps, T: int) -> jnp.ndarray:
    """Chunk to chunk: ``Δ = U − W S``; ``o = qe S + P Δ``; ``S ← keeps · S
    + kdᵀ Δ`` — the blocks [B, Z, G, r, Q, .] a chunk, ``keeps`` a scalar
    a chunk and head ([B, Z, G, r]) or one a key channel ([B, Z, G, r,
    dk]). Returns o [B, T, H, dv] float32."""
    B_, Z, G, r, Q, dv = U.shape
    dk = W.shape[-1]
    cd, f32 = W.dtype, jnp.float32
    by_channel = keeps.ndim == 5

    def step(S, xs):  # S [B, G, r, dk, dv] float32
        U_z, W_z, qe_z, kd_z, P_z, keeps_z = xs
        Sc = S.astype(cd)
        delta = U_z - jnp.einsum("bgrik,bgrkv->bgriv", W_z, Sc,
                                 preferred_element_type=f32)
        dc = delta.astype(cd)
        o = (jnp.einsum("bgrik,bgrkv->bgriv", qe_z, Sc,
                        preferred_element_type=f32)
             + jnp.einsum("bgrij,bgrjv->bgriv", P_z, dc,
                          preferred_element_type=f32))
        kept = keeps_z[..., None] if by_channel else keeps_z[..., None, None]
        S = kept * S + jnp.einsum(
            "bgrik,bgriv->bgrkv", kd_z, dc, preferred_element_type=f32)
        return S, o

    _, o = jax.lax.scan(
        step, jnp.zeros((B_, G, r, dk, dv), f32),
        tuple(jnp.moveaxis(a, 1, 0) for a in (U, W, qe, kd, P, keeps)))
    # [Z, B, G, r, Q, dv] -> [B, T, H, dv]
    o = jnp.moveaxis(o, (0, 4), (1, 2)).reshape(B_, Z * Q, G * r, dv)
    return o[:, :T]


def _rule_impl(impl: str, chunk: int, G: int, H: int, dk: int, dv: int,
               dtype) -> str:
    """"pallas" | "pallas_interpret" | "xla" (as ``ssm._ssd_impl``): the
    kernels where they take the shapes and, compiled, where ``impl`` and
    the platform ask for a kernel and the chip has the VMEM."""
    from areal_tpu.ops.attention import _wants_kernel
    from areal_tpu.ops.pallas import gated_delta_rule as kernel

    if not kernel.supported(chunk, G, H, dk, dv, dtype):
        return "xla"
    if impl == "pallas_interpret":
        return impl
    return "pallas" if _wants_kernel(impl) and kernel.fits_device() else "xla"


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _rule_kernel(q, k, v, g, beta, seg, chunk, how):
    """The kernels' rule: q, k, v in one compute dtype, g and beta
    float32, T a whole number of chunks."""
    from areal_tpu.ops.pallas import gated_delta_rule as kernel

    with jax.named_scope("gdn_rule"):
        return kernel.rule_fwd(q, k, v, g, beta, seg, chunk,
                               interpret=how == "pallas_interpret")[0]


def _rule_kernel_fwd(q, k, v, g, beta, seg, chunk, how):
    from areal_tpu.ops.pallas import gated_delta_rule as kernel

    with jax.named_scope("gdn_rule"):
        o, states = kernel.rule_fwd(q, k, v, g, beta, seg, chunk, keep=True,
                                    interpret=how == "pallas_interpret")
    return o, (q, k, v, g, beta, seg, states)


def _rule_kernel_bwd(chunk, how, res, do):
    from areal_tpu.ops.pallas import gated_delta_rule as kernel

    q, k, v, g, beta, seg, states = res
    with jax.named_scope("gdn_rule"):
        return kernel.rule_bwd(q, k, v, g, beta, seg, states, do, chunk,
                               interpret=how == "pallas_interpret") + (None,)


_rule_kernel.defvjp(_rule_kernel_fwd, _rule_kernel_bwd)


def rule_with_norms(q: jnp.ndarray,  # [B, T, G, dk] as the convolution
                    k: jnp.ndarray,  # leaves them: NOT normalised
                    v: jnp.ndarray,  # [B, T, H, dv]
                    z: jnp.ndarray,  # [B, T, H · dv] the output's gate
                    w: jnp.ndarray,  # [dv] the gated norm's weight
                    g: jnp.ndarray, beta: jnp.ndarray,  # [B, T, H]
                    seg: jnp.ndarray, chunk: int, eps: float,
                    how: str) -> jnp.ndarray:
    """The mixer between its convolution and its output projection as the
    ONE kernel pair: q̂, k̂ l2-normalised on the way in, the rule, and ``y =
    rms(o) · w ⊙ silu(z)`` [B, T, H · dv] on the way out in the compute
    dtype — the arithmetic and the rounding points of :func:`gdn_mixer`'s
    XLA text, with no array by head outside the kernels. ``how``: "pallas"
    | "pallas_interpret", as :func:`_rule_impl` answered (the XLA form of
    this entry is the mixer's own text)."""
    T = q.shape[1]
    _RULE_IMPL[how] += 1
    pad = -T % chunk
    if pad:  # as gated_delta_rule: a zero row norms to zero and writes none
        q, k, v, z, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, z, g, beta))
        seg = jnp.pad(seg, ((0, 0), (0, pad)))
    cd, f32 = v.dtype, jnp.float32
    return _normed_kernel(q.astype(cd), k.astype(cd), v, z.astype(cd), w,
                          g.astype(f32), beta.astype(f32), seg, chunk,
                          float(eps), how)[:, :T]


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10))
def _normed_kernel(q, k, v, z, w, g, beta, seg, chunk, eps, how):
    """The kernels with the mixer's norms inside: q, k raw, T a whole
    number of chunks; y [B, T, H · dv] in the compute dtype."""
    from areal_tpu.ops.pallas import gated_delta_rule as kernel

    with jax.named_scope("gdn_rule"):
        return kernel.rule_fwd(q, k, v, g, beta, seg, chunk,
                               interpret=how == "pallas_interpret",
                               norms=(z, w, L2_EPS, eps))[0]


def _normed_kernel_fwd(q, k, v, z, w, g, beta, seg, chunk, eps, how):
    from areal_tpu.ops.pallas import gated_delta_rule as kernel

    with jax.named_scope("gdn_rule"):
        y, states = kernel.rule_fwd(q, k, v, g, beta, seg, chunk, keep=True,
                                    interpret=how == "pallas_interpret",
                                    norms=(z, w, L2_EPS, eps))
    return y, (q, k, v, z, w, g, beta, seg, states)


def _normed_kernel_bwd(chunk, eps, how, res, dy):
    from areal_tpu.ops.pallas import gated_delta_rule as kernel

    q, k, v, z, w, g, beta, seg, states = res
    with jax.named_scope("gdn_rule"):
        dq, dk, dv, dg, dbeta, dz, dw = kernel.rule_bwd(
            q, k, v, g, beta, seg, states, dy, chunk,
            interpret=how == "pallas_interpret", norms=(z, w, L2_EPS, eps))
    return dq, dk, dv, dz, dw.astype(w.dtype), dg, dbeta, None


_normed_kernel.defvjp(_normed_kernel_fwd, _normed_kernel_bwd)


# On the XLA path the mixer's per-head work — convolution, gates, the
# rule, the gated norm — runs a group of key heads at a time (``lax.map``),
# each group under its own checkpoint: the float32 blocks of a chunk (A,
# its inverse, the decays) and the states the backward pass reads then
# exist for one group at a time, at the price of one more forward of the
# group in the backward pass. Groups of a model: gcd(key heads,
# _HEAD_GROUPS). The XLA form's only: the kernels keep those blocks in
# VMEM, and the mixer then runs all heads at once with nothing run twice —
# and with NOTHING by head outside the kernels (:func:`rule_with_norms`):
# at 16 / 32 heads at once XLA tiled the (heads, 128) views of q, k and o
# for the two norms and copied the [T, 2048] / [T, 4096] arrays into that
# tiling and back (13.7 % of the Qwen3-Next cell's busy time, more than
# the rule), and kept float32 copies of q, k and o for the backward (1.8
# GB at a 16,384-token row); the kernels hold one key head's lanes a grid
# step, where a head's norm is a lane reduction (PERF.md §6, PR 53 / 55).
_HEAD_GROUPS = 4


def gdn_mixer(u: jnp.ndarray,  # [B, T, D] the normed residual stream
              lp: Dict[str, jnp.ndarray],  # this layer's parameters
              gdn: GDNConfig, eps: float,
              segment_ids: Optional[jnp.ndarray],  # None = one document a row
              impl: str = "auto",  # the model's ``attn_impl``
              ) -> jnp.ndarray:
    B_, T, _ = u.shape
    G, H, dk, dv = gdn.n_k_heads, gdn.n_v_heads, gdn.k_head_dim, gdn.v_head_dim
    kdim = gdn.key_dim
    f32 = jnp.float32
    seg = (jnp.ones((B_, T), jnp.int32) if segment_ids is None
           else segment_ids)
    _GEOMETRY[(B_, T, gdn.chunk_size, G, H, dk, dv)] += 1
    how = _rule_impl(impl, gdn.chunk_size, G, H, dk, dv, u.dtype)
    kernel = how != "xla"
    _MIXER_NORMS["kernel" if kernel else "xla"] += 1
    n = 1 if kernel else math.gcd(G, _HEAD_GROUPS)
    Gn, Hn = G // n, H // n

    def by_group(a):  # [..., n * width] -> [n, ..., width]
        return jnp.moveaxis(
            a.reshape(a.shape[:-1] + (n, a.shape[-1] // n)), -2, 0)

    with jax.named_scope("gdn_in_proj"):
        qkv, z = jnp.split(u @ lp["gdn_qkvz"], [gdn.conv_dim], axis=-1)
        b, a = jnp.split(u @ lp["gdn_ba"], 2, axis=-1)
        q, k, v = jnp.split(qkv, [kdim, 2 * kdim], axis=-1)
        wq, wk, wv = jnp.split(lp["gdn_conv"], [kdim, 2 * kdim], axis=-1)
        xs = (by_group(q), by_group(k), by_group(v),
              by_group(z), by_group(b), by_group(a),
              by_group(wq), by_group(wk), by_group(wv),
              by_group(lp["gdn_A_log"]), by_group(lp["gdn_dt_bias"]))

    def heads(xs):
        q, k, v, z, b, a, wq, wk, wv, A_log, dt_bias = xs
        with jax.named_scope("gdn_conv"):
            q, k, v = (jax.nn.silu(causal_conv(x, w, 0.0, seg))
                       for x, w in ((q, wq), (k, wk), (v, wv)))
        with jax.named_scope("gdn_gates"):
            beta = jax.nn.sigmoid(b.astype(f32))
            g = -jnp.exp(A_log.astype(f32)) * jax.nn.softplus(
                a.astype(f32) + dt_bias.astype(f32))
        if kernel:  # both norms inside the kernels: nothing by head here
            with jax.named_scope("gdn_rule"):
                return rule_with_norms(
                    q.reshape(B_, T, Gn, dk), k.reshape(B_, T, Gn, dk),
                    v.reshape(B_, T, Hn, dv), z, lp["gdn_norm"], g, beta,
                    seg, gdn.chunk_size, eps, how)
        with jax.named_scope("gdn_gates"):
            q = (l2_normalize(q.reshape(B_, T, Gn, dk)) * dk ** -0.5
                 ).astype(u.dtype)
            k = l2_normalize(k.reshape(B_, T, Gn, dk)).astype(u.dtype)
        with jax.named_scope("gdn_rule"):
            o = gated_delta_rule(q, k, v.reshape(B_, T, Hn, dv), g, beta,
                                 seg, gdn.chunk_size, impl)
        with jax.named_scope("gdn_gate_norm"):
            o = o * jax.lax.rsqrt(
                jnp.mean(o * o, axis=-1, keepdims=True) + eps)
            y = (o * lp["gdn_norm"].astype(f32)).astype(u.dtype)
            return (y.reshape(B_, T, Hn * dv).astype(f32)
                    * jax.nn.silu(z.astype(f32))).astype(u.dtype)

    y = jax.lax.map(jax.checkpoint(heads), xs) if n > 1 else heads(
        jax.tree.map(lambda a: a[0], xs))[None]
    with jax.named_scope("gdn_out_proj"):
        return jnp.moveaxis(y, 0, 2).reshape(B_, T, H * dv) @ lp["gdn_out"]


def resets_in_chunk(starts, row_len: int, chunk: int) -> int:
    """Of the token offsets ``starts`` at which a row's documents begin,
    those that fall INSIDE a chunk of the rule (not on the chunk grid):
    each masks a chunk's triangular matrices and the state it reads."""
    return sum(0 < s < row_len and s % chunk != 0 for s in starts)
