"""TPU chunked gated delta rule (Gated DeltaNet) for packed segment batches.

    S ← e^{g_t} S;  δ_t = β_t (v_t − Sᵀ k_t);  S ← S + k_t δ_tᵀ;  o_t = Sᵀ q_t

(S [dk, dv] float32 a value head, zero before a document's first token) —
the chunked algorithm of ``models/gdn.gated_delta_rule`` at the same chunk
Q, one grid step a (row, key head, run of chunks), the chunk axis innermost
and sequential. With ``c`` the cumulated ``g`` inside a chunk and ``S₀``
the state entering it, per value head:

    A  = β_i (k_i·k_j) e^{c_i − c_j}      j < i, one document; else exactly 0
    M  = (I + A)^-1
    Δ  = M · [β ⊙ (v − e ⊙ (k S₀))]       e_i = e^{c_i} on the tokens still in
                                           the document the row entered in
    o  = e ⊙ (q S₀) + P Δ                 P = q·kᵀ ⊙ e^{c_i − c_j}, j <= i
    S₁ = κ S₀ + (t ⊙ k)ᵀ Δ                t_j = e^{c_Q − c_j} on the tokens of
                                           the document the chunk ends in, κ =
                                           e^{c_Q} where that is the entering one

Every exponent is that of a non-positive difference (the argument is 0
where the mask drops it and the result is replaced by exactly 0: the
contract of ``ssm._masked_exp``). Nothing [Q, Q] leaves VMEM. ``A``, ``M``,
the decays and the state are float32; matmul operands are the compute
dtype and every sum float32.

**A key head's value heads ride as a PAIR** along the lanes: ``[A₀ | A₁]``
[Q, 2 Q] is one full-lane array, ``k·[k; k]ᵀ`` makes ``k·kᵀ`` twice, and a
pair times ``[[x₀, 0], [0, x₁]]`` (:func:`_blocks`) is ``[a₀ x₀ | a₁ x₁]`` —
one MXU product of 128 deep where two of 64 stood; a head's [Q, dv] arrays
sit side by side the same way ([Q, r · dv], as v and o are laid out in HBM).

**A step works in two phases.** First everything no state enters, for ALL
the step's chunks at once ([chunks, ., .] arrays and one batched product an
operation: the chunks' chains of dependent products run side by side, and
the kernel's text does not grow with the chunks a step holds): the masks
and decays, ``A``, ``M`` and — forward — with ``U = M (β v)``, ``W = M (β e
k)`` (so that ``Δ = U − W S₀``): ``G = (t ⊙ k)ᵀ W``, ``C = (t ⊙ k)ᵀ U``,
``q̃ = e ⊙ q − P W`` and ``P U``, into VMEM scratches. Then the states'
chain, a ``fori_loop`` over the chunks with ONE product a chunk and value
head: ``[G; q̃] · S₀``, ``S₁ = κ S₀ + C − G S₀``, ``o = q̃ S₀ + P U``. The
state is a VMEM scratch that rides the chunk axis.

**The inverse** goes by blocks (:func:`_inverses`): ``M − M L M`` from
blocks of 2 up to Q, ten products whose factors are no larger than the
inverse's own blocks. Float32 operands multiply at ``HIGHEST``; under a
16-bit compute dtype the left factor is split into two bfloat16 parts
(2^-16) and the right one rounded (as ``M`` itself is, before it is used).

**The gates' layout.** ``c``, β, the segment ids, ``c_Q`` and the
documents at the chunk's two ends ride ONE [8, 128] float32 tile a chunk
and key head (:func:`gate_tiles`: tokens in the lanes, a quantity a
sublane); its transpose gives the same quantities down the sublanes. The
backward writes d c and d β into a tile of its own.

 - forward (:func:`rule_fwd`): q, k [R, T, G·dk], v [R, T, H·dv] as the
   mixer has them (tokens major, heads in the lanes) and the gate tiles
   in; o float32 out and — for a backward pass — the state ENTERING each chunk
   in the compute dtype (the state enters every product rounded to it;
   the carried one is float32);
 - backward (:func:`rule_bwd`): the same grid with the chunks reversed,
   ``dS`` as its carry, in the forward's phases. With ``W``, ``G``, ``q̃``
   as the forward's, a chunk's ``dS₀ = κ dS₁ − Gᵀ dS₁ + q̃ᵀ do``: FIRST,
   for all the step's chunks at once, the blocks again from q, k, v, the
   tiles and the kept states (``Δ = M (β (v − e ⊙ k S₀))`` rides the
   product that makes ``W``), then ``Gᵀ`` and ``q̃ᵀ do`` into VMEM
   scratches; THEN the chain from the step's last chunk to its first, one
   product a chunk and value head, each chunk's ``dS₁`` left in a scratch;
   LAST every gradient from those ``dS₁``, for all the chunks at once. The
   inverse needs no cotangent of its own: with ``dR = Mᵀ dΔ``, ``dA = −Mᵀ
   (dΔ Rᵀ) Mᵀ = −dR Δᵀ``. The decays' gradient is row sums less column
   sums of ``dA ⊙ A + dP ⊙ P`` plus three sums over a token's channels,
   made on the MXU against 0/1 rows (so they come out with tokens in the
   lanes). A loop that walked the chunks and did all of this inside took
   9.99 ms a 14,336-token row where this takes 5.58 (PERF.md §6, PR 57).

**The mixer's two norms inside** (``norms``, a static flag of the same two
kernels: what ``models/gdn.rule_with_norms`` runs). A grid step holds ONE
key head's 128 lanes of q and k and its value heads' 128 lanes each of o,
so both of the mixer's per-head normalisations are lane reductions over
blocks that are in VMEM anyway, and no XLA op sees q, k or o by head:

 - on the way in q and k arrive as the convolution leaves them and are
   l2-normalised — ``x · rsqrt(Σ x² + eps)`` in float32, q times ``dk **
   -0.5`` — and rounded to the compute dtype where the mixer rounds, for
   all the step's chunks at once (:func:`_prepare`, once in either
   kernel);
 - on the way out the forward keeps the step's o in VMEM and, after the
   states' chain, writes ``y = ((o · rsqrt(mean(o²) + eps) · w).astype(cd)
   · silu(z)).astype(cd)`` a value head — z [R, T, H·dv] and the norm's
   weight are two more inputs — in the COMPUTE dtype: no float32 o in HBM;
 - the backward takes d y, REBUILDS the chunks' o from the kept states and
   the blocks it rebuilds anyway (``e ⊙ (q S₀) + P Δ``: ``P Δ`` rides the
   product that makes ``P W``, nothing kept for it), forms d o through the
   gate and the norm,
   writes d z and the weight's gradient (a block a (row, key head), summed
   over the row's steps where it stays) and sends d q̂, d k̂ through the l2
   norm: ``dx = (dx̂ − u (u · dx̂)) · rsqrt(Σ x² + eps)``, ``u`` the
   unrounded unit vector.

The kernels' device ops are named ``gdn_rule_fwd`` / ``gdn_rule_bwd``
under the caller's scope (not jitted by themselves: the benchmark reads
the rule by its scope). CPU/testing: ``interpret=True``;
tests/test_tpu_compile.py compiles them for a described v5e.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# fits_device: the same VMEM_LIMIT, asked of the attached chip
from areal_tpu.ops.pallas.ssd_scan import chunk_cumsum, fits_device  # noqa: F401

LANE = 128
SUBLANE = 8
FWD_NAME, BWD_NAME = "gdn_rule_fwd", "gdn_rule_bwd"
# Chunks a grid step at most (tools/gdn_rule_sweep.py; PERF.md §5, PR 53):
# the first phase of a step runs its chunks' chains side by side, and at 1
# x 14,336 the forward / backward kernels read 3.89 / 8.68 ms at 4, 2.87 /
# 7.68 at 8, 2.77 / 7.45 at 16 (whose first phase holds twice the VMEM).
CHUNKS_PER_STEP = 8
# The backward's own, since all three of its phases hold a step's chunks
# (PERF.md §6, PR 57; with the norms inside, 1 x 14,336): 9.90 ms at 2, 7.09
# at 4, 5.64 at 8, 5.53 at 16 — which compiles in 6 s for 8's 2.6 and does
# not divide the 136 chunks of the cell's other row.
BWD_CHUNKS_PER_STEP = 8
VMEM_LIMIT = 64 * 1024 * 1024

_NT = (((1,), (1,)), ((), ()))  # a · bᵀ
_TN = (((0,), (0,)), ((), ()))  # aᵀ · b
_HIGHEST = jax.lax.Precision.HIGHEST


def supported(chunk: int, k_heads: int, v_heads: int, dk: int, dv: int,
              dtype) -> bool:
    """What the kernels take: chunks of 64, heads of one lane tile, one or
    two value heads a key head (a chunk's gates ride one [8, 128] tile: 3r
    + 1 rows), float32 or bfloat16."""
    r = v_heads // max(k_heads, 1)
    return (chunk == 64 and dk == LANE and dv == LANE
            and v_heads == r * k_heads and 1 <= r <= 2
            and jnp.dtype(dtype) in (jnp.dtype(jnp.float32),
                                     jnp.dtype(jnp.bfloat16)))


def chunks_per_step(chunks: int, backward: bool = False) -> int:
    """The most chunks a step (<= ``CHUNKS_PER_STEP``, or the backward's
    ``BWD_CHUNKS_PER_STEP``; a power of two) that divide a row's."""
    n = BWD_CHUNKS_PER_STEP if backward else CHUNKS_PER_STEP
    while chunks % n:
        n //= 2
    return n


def _dot(a, b, dims=None, exact=False):
    kw = dict(preferred_element_type=jnp.float32,
              precision=_HIGHEST if exact else None)
    if dims is None:
        return jnp.dot(a, b, **kw)
    return jax.lax.dot_general(a, b, dims, **kw)


def _dots(spec: str, a, b, exact: bool):
    """A product a chunk of a step: [chunks, ., .] operands, one op."""
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32,
                      precision=_HIGHEST if exact else None)


def _lane(shape):
    return jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)


def _by_head(vals, shape, width: int):
    """Per-head [.., Q, 1] columns or [.., 1, 1]-free rows -> ``shape``,
    head h's on its own ``width`` lanes."""
    if len(vals) == 1:
        return jnp.broadcast_to(vals[0], shape)
    return jnp.where(_lane(shape) < width, vals[0], vals[1])


def _blocks(x, r: int, width: int):
    """Head h's lanes of x [.., Q, r · width] in the rows of block h, zeros
    elsewhere: [.., 2 Q, r · width] = [[x₀, 0], [0, x₁]] — the right factor
    under which a PAIR [a₀ | a₁] [Q, 2 Q] multiplies each head's own: ``[a₀
    | a₁] · blocks(x) = [a₀ x₀ | a₁ x₁]`` (one head: ``[[x₀], [0]]``)."""
    zero = jnp.zeros_like(x)
    if r == 1:
        return jnp.concatenate([x, zero], axis=-2)
    left = _lane(x.shape) < width
    return jnp.concatenate([jnp.where(left, x, zero),
                            jnp.where(left, zero, x)], axis=-2)


def _diagonal(x, r: int, width: int):
    """[.., 2 Q, r · width] -> [.., Q, r · width]: block (h, h) of each
    head."""
    Q = x.shape[-2] // 2
    if r == 1:
        return x[..., :Q, :]
    return jnp.concatenate([x[..., :Q, :width], x[..., Q:, width:]], axis=-1)


def _products(a, b, exact: bool):
    """PAIRS [a₀ | a₁] of float32 [Q, Q] matrices, a pair a chunk ([chunks,
    Q, 2 Q]), twice -> their products [a₀ b₀ | a₁ b₁]. Float32 at
    ``HIGHEST`` where the compute dtype is float32 (``exact``); else the
    left factor as two bfloat16 parts (one product of 2 Q rows) against
    the right one rounded."""
    Q = a.shape[1]
    if exact:
        return _dots("nij,njk->nik", a, _blocks(b, 2, Q), True)
    hi = a.astype(jnp.bfloat16)
    lo = (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    both = _dots("nij,njk->nik", jnp.concatenate([hi, lo], axis=1),
                 _blocks(b.astype(jnp.bfloat16), 2, Q), False)
    return both[:, :Q] + both[:, Q:]


def _inverses(A, exact: bool):
    """``(I + A)^-1`` of pairs [A₀ | A₁] of strictly lower-triangular
    float32 [Q, Q] matrices, a pair a chunk of the step ([chunks, Q, 2
    Q]; the chunks' chains of dependent products overlap). By blocks: with
    the inverse ``M`` of the diagonal blocks of width w known and ``L``
    what ``A`` holds between the two halves of each block of 2w, the
    inverse at 2w is ``M − M L M`` (``[[M₁, 0], [−M₂ A₂₁ M₁, M₂]]``); at w
    = 2 it is ``I − L``. Ten products for Q = 64, each of factors no
    larger than the inverse's own blocks — the sum of ``(−A)ⁿ`` that the
    XLA form makes in as many takes powers whose entries grow with the
    binomials where a chunk's keys resemble each other, and cancels
    them."""
    Q = A.shape[1]
    ii = jax.lax.broadcasted_iota(jnp.int32, A.shape, 1)
    jj = _lane(A.shape) & (Q - 1)
    apart = ii ^ jj  # < w: one block of width w (a power of two)
    M = jnp.where(ii == jj, 1.0, 0.0) - jnp.where(apart < 2, A, 0.0)
    w = 2
    while w < Q:
        L = jnp.where((apart >= w) & (apart < 2 * w), A, 0.0)
        M = M - _products(M, _products(L, M, exact), exact)
        w *= 2
    return M


def _lane_sums(F, ranges, exact: bool):
    """float32 F [chunks, Q, W] -> [chunks, 16, Q] float32 whose row a is
    the sum of F over the lanes ``ranges[a]`` = (first, width), a token a
    lane: one 0/1 product a chunk, F as three bfloat16 parts (or at
    ``HIGHEST``)."""
    shape = (F.shape[0], 2 * SUBLANE, F.shape[2])
    lane = _lane(shape)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    sel = jnp.zeros(shape, jnp.bool_)
    for a, (first, width) in enumerate(ranges):
        sel = sel | ((row == a) & (lane >= first) & (lane < first + width))
    if exact:
        return _dots("naw,nqw->naq", sel.astype(jnp.float32), F, True)
    pick = sel.astype(jnp.bfloat16)
    out = None
    for _ in range(3):
        part = F.astype(jnp.bfloat16)
        F = F - part.astype(jnp.float32)
        d = _dots("naw,nqw->naq", pick, part, False)
        out = d if out is None else out + d
    return out


# The rows of a chunk's gate tile (:func:`gate_tiles`).
_C, _BETA, _SEG, _C_END, _C_SECOND, _PREV, _LAST = 0, 1, 2, 3, 5, 6, 7


def _row(tile, row: int):
    return tile[..., row:row + 1, :]


def _column(cols, Q: int, h: int, row: int):
    """Head h's [.., Q, 1] of a PAIRED row of the tile, from its
    transpose (``cols``: the same quantities, a token a sublane)."""
    return cols[..., h * Q:(h + 1) * Q, row:row + 1]


def _pair_blocks(tile, cols, Q: int, r: int):
    """What the gate tiles ([.., 8, 128], one chunk or all of a step's)
    give BEFORE any state is known, the value heads paired along the lanes
    ([.., Q, 2 Q]): β down the sublanes and the two masked decay blocks (a
    single head: zeros on the second's lanes)."""
    shape = tile.shape[:-2] + (Q, 2 * Q)
    ii = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 2)
    lane = _lane(shape)
    jj = lane & (Q - 1)
    same = _column(cols, Q, 0, _SEG) == _row(tile, _SEG)
    if r == 1:
        same = same & (lane < Q)
    lo, up = same & (ii > jj), same & (ii >= jj)
    c_c = _by_head([_column(cols, Q, h, _C) for h in range(r)], shape, Q)
    E = jnp.exp(jnp.where(up, c_c - _row(tile, _C), 0.0))
    b_c = _by_head([_column(cols, Q, h, _BETA) for h in range(r)], shape, Q)
    return b_c, jnp.where(lo, E, 0.0), jnp.where(up, E, 0.0)


def _end_blocks(tile, cols, Q: int, r: int, dv: int):
    """... and what ties a chunk to the states, a head on its own dv lanes
    ([.., Q, r · dv] / [.., 1, r · dv]): β, e, t down the sublanes and κ
    of the module's docstring; and per head t down the sublanes and, for
    the backward's sums, e and t along the lanes ([.., 1, Q])."""
    prev_r, last_r = _row(tile, _PREV), _row(tile, _LAST)
    prev_c, last_c = (_column(cols, Q, 0, a) for a in (_PREV, _LAST))
    seg_r, seg_c = _row(tile, _SEG)[..., :Q], _column(cols, Q, 0, _SEG)
    wide = tile.shape[:-2] + (Q, r * dv)
    e_c, t_c, kappa, e_r, t_r = [], [], [], [], []
    for h in range(r):
        c_c = _column(cols, Q, h, _C)
        c_r = _row(tile, _C if h == 0 else _C_SECOND)[..., :Q]
        end_r = _row(tile, _C_END + h)  # c_Q on every lane
        e_c.append(jnp.where(seg_c == prev_c, jnp.exp(c_c), 0.0))
        t_c.append(jnp.where(
            seg_c == last_c,
            jnp.exp(_column(cols, Q, 0, _C_END + h) - c_c), 0.0))
        kappa.append(jnp.where(prev_r == last_r, jnp.exp(end_r), 0.0))
        e_r.append(jnp.where(seg_r == prev_r[..., :Q], jnp.exp(c_r), 0.0))
        t_r.append(jnp.where(seg_r == last_r[..., :Q],
                             jnp.exp(end_r[..., :Q] - c_r), 0.0))
    return dict(
        b=_by_head([_column(cols, Q, h, _BETA) for h in range(r)], wide, dv),
        e=_by_head(e_c, wide, dv), t=_by_head(t_c, wide, dv), t_c=t_c,
        kappa=kappa[0] if r == 1 else jnp.concatenate(kappa, axis=-1),
        e_r=e_r, t_r=t_r)


def _l2_parts(x, eps: float):
    """A head's raw q or k [.., Q, dk] as the convolution leaves it -> (x ·
    rsqrt(Σ x² + eps), the rsqrt [.., Q, 1]): float32, a lane reduction a
    token (``gdn.l2_normalize``)."""
    xf = x.astype(jnp.float32)
    rs = jax.lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True) + eps)
    return xf * rs, rs


def _prepare(q_ref, k_ref, gate_ref, Q: int, r: int, l2_eps):
    """The part of a step that neither a state nor a cotangent enters, for
    all its chunks at once ([chunks, ., .] arrays: one basic block, the
    chunks' chains of products side by side): the gate tiles and their
    transposes (``tiles``, ``cols``), ``q`` and ``k`` in the compute dtype,
    ``k2`` = [k; k] (``·k2ᵀ`` makes a product against k twice along the
    lanes), β down the sublanes and the two masked decay blocks of each
    head, paired (``b_c``, ``D_lo``, ``D_up``), ``kk`` = k·kᵀ, each chunk's
    pair ``M`` = [M₀ | M₁] in the compute dtype and ``P`` = q·kᵀ under each
    head's decays, float32. With ``l2_eps`` q and k arrive raw and are
    l2-normalised here (q times ``dk ** -0.5``), rounded to the compute
    dtype as the mixer rounds; ``l2`` then holds the float32 unit vectors
    and the rsqrt of q and of k (:func:`_l2_parts`)."""
    cd = q_ref.dtype
    exact = cd == jnp.float32
    nc = gate_ref.shape[2]
    dk = k_ref.shape[2]
    tiles = gate_ref[0, 0]  # [chunks, 8, 128]
    cols = jnp.swapaxes(tiles, 1, 2)
    k = k_ref[0].reshape(nc, Q, dk)
    if l2_eps is not None:
        uk, rk = _l2_parts(k, l2_eps)
        k = uk.astype(cd)
    k2 = jnp.concatenate([k, k], axis=1)
    b_c, D_lo, D_up = _pair_blocks(tiles, cols, Q, r)
    kk = _dots("nik,njk->nij", k, k2, exact)
    M = _inverses(b_c * kk * D_lo, exact).astype(cd)
    q = q_ref[0].reshape(nc, Q, dk)
    l2 = None
    if l2_eps is not None:
        uq, rq = _l2_parts(q, l2_eps)
        q = (uq * dk ** -0.5).astype(cd)
        l2 = (uq, rq, uk, rk)
    P = _dots("nik,njk->nij", q, k2, exact) * D_up
    return dict(tiles=tiles, cols=cols, q=q, k=k, k2=k2, b_c=b_c, D_lo=D_lo,
                D_up=D_up, kk=kk, M=M, P=P, l2=l2)


def _head_means(x, dv: int):
    """float32 x [.., Q, r · dv], a value head its own dv lanes -> the mean
    over each head's lanes, laid out as x (a lane reduction a head)."""
    means = [jnp.broadcast_to(
        jnp.mean(x[..., h * dv:(h + 1) * dv], axis=-1, keepdims=True),
        x.shape[:-1] + (dv,)) for h in range(x.shape[-1] // dv)]
    return means[0] if len(means) == 1 else jnp.concatenate(means, axis=-1)


def _rms_parts(o, eps: float, dv: int):
    """float32 o [.., Q, r · dv] -> (o · rstd, rstd laid out as o): the RMS
    norm's statistics a head and token, ``rsqrt(mean(o²) + eps)``."""
    rstd = jax.lax.rsqrt(_head_means(o * o, dv) + eps)
    return o * rstd, rstd


def _fwd_kernel(*refs, Q: int, r: int, keep: bool, norms):
    """A step of the forward: first everything no state enters, for all the
    step's chunks at once — with ``U = M (β v)``, ``W = M (β e k)`` (so
    that ``Δ = U − W S₀``): ``G = (t ⊙ k)ᵀ W``, ``C = (t ⊙ k)ᵀ U``, ``q̃ =
    e ⊙ q − P W`` and ``P U`` —; then the states' chain, ONE product a
    chunk and value head: ``[G; q̃] · S₀``, ``S₁ = κ S₀ + C − G S₀``, ``o
    = q̃ S₀ + P U``. With ``norms`` (l2 eps, rms eps) q and k arrive raw and
    are normalised on the way in (:func:`_prepare`), z and the gated norm's
    weight are two more inputs, and what leaves is the mixer's ``y`` in the
    compute dtype: the step's o stay in VMEM (``pu_ref``) and are normed
    and gated for all its chunks at once after the states' chain."""
    n_in = 6 if norms else 4
    q_ref, k_ref, v_ref, gate_ref = refs[:4]
    o_ref = refs[n_in]
    s_ref = refs[n_in + 1] if keep else None
    state, gq_ref, c_ref, pu_ref, kap_ref = refs[-5:]
    z = pl.program_id(2)
    nc = gate_ref.shape[2]
    dk, dv = q_ref.shape[2], v_ref.shape[2] // r
    W = r * dv
    cd, f32 = q_ref.dtype, jnp.float32
    exact = cd == f32

    @pl.when(z == 0)
    def _():
        state[...] = jnp.zeros(state.shape, state.dtype)

    pre = _prepare(q_ref, k_ref, gate_ref, Q, r, norms and norms[0])
    q, k, M, P = pre["q"], pre["k"], pre["M"], pre["P"].astype(cd)
    hd = _end_blocks(pre["tiles"], pre["cols"], Q, r, dv)
    kf, qf = k.astype(f32), q.astype(f32)
    if r > 1:  # a head's copy on its own lanes
        kf, qf = (jnp.concatenate([a] * r, axis=2) for a in (kf, qf))
    v = v_ref[0].reshape(nc, Q, W).astype(f32)
    UW = _dots("nij,njk->nik", M, jnp.concatenate(
        [_blocks((hd["b"] * v).astype(cd), r, dv),
         _blocks((hd["b"] * hd["e"] * kf).astype(cd), r, dv)], axis=2),
        exact)  # [chunks, Q, 2 W]: U | W
    GC = _dots("nqk,nqw->nkw", k, jnp.concatenate(
        [(hd["t"] * UW[..., W:]).astype(cd),
         (hd["t"] * UW[..., :W]).astype(cd)], axis=2), exact)  # G | C
    PWU = _dots("nij,njk->nik", P, jnp.concatenate(
        [_blocks(UW[..., W:].astype(cd), r, dv),
         _blocks(UW[..., :W].astype(cd), r, dv)], axis=2), exact)
    qe = (hd["e"] * qf - PWU[..., :W]).astype(cd)  # q̃
    for h in range(r):
        at = slice(h * dv, (h + 1) * dv)
        gq_ref[:, h, :dk, :] = GC[:, :, at].astype(cd)
        gq_ref[:, h, dk:, :] = qe[:, :, at]
    c_ref[...] = GC[..., W:]
    pu_ref[...] = PWU[..., W:]
    kap_ref[...] = jnp.broadcast_to(hd["kappa"], kap_ref.shape)

    def chunk(c, carry):
        rows = pl.ds(pl.multiple_of(c * Q, Q), Q)
        S0 = state[...]  # [dk, r · dv]: a head on its own dv lanes
        S0c = S0.astype(cd)
        if keep:
            s_ref[0, c, 0] = S0c
        outs = [_dot(gq_ref[c, h], S0c[:, h * dv:(h + 1) * dv], None, exact)
                for h in range(r)]  # [G; q̃] · S₀
        out = jnp.concatenate(outs, axis=1) if r > 1 else outs[0]
        state[...] = kap_ref[c, :1, :] * S0 + c_ref[c] - out[:dk]
        if norms:
            pu_ref[c] = out[dk:] + pu_ref[c]
        else:
            o_ref[0, rows, :] = out[dk:] + pu_ref[c]
        return carry

    jax.lax.fori_loop(0, nc, chunk, 0)
    if norms:
        z_ref, w_ref = refs[4:6]
        y = (_rms_parts(pu_ref[...], norms[1], dv)[0] * w_ref[...]).astype(cd)
        gate = jax.nn.silu(z_ref[0].reshape(nc, Q, W).astype(f32))
        o_ref[0] = (y.astype(f32) * gate).astype(cd).reshape(nc * Q, W)


def _bwd_kernel(*refs, Q: int, r: int, norms):
    """A step of the backward, in the forward's phases (the module's
    docstring): everything no ``dS`` enters for all the step's chunks at
    once, the ``dS`` chain with ONE product a chunk and value head, then
    every gradient from the chain's ``dS`` for all the chunks at once. With
    ``norms`` q and k arrive raw, ``do_ref`` holds the cotangent of the
    mixer's ``y``, z and the gated norm's weight are two more inputs and d
    z and the weight's gradient (summed over a row's steps in its block)
    two more outputs: the chunks' o are rebuilt from the kept states and
    the blocks the step makes anyway (``P Δ`` rides the product that makes
    ``P W``), ``do`` formed through the gated norm, and d q̂, d k̂ leave
    through the l2 norm."""
    n_in = 8 if norms else 6
    q_ref, k_ref, v_ref, gate_ref, s_ref, do_ref = refs[:6]
    dq_ref, dk_ref, dv_ref, dgate_ref = refs[n_in:n_in + 4]
    dstate, gt_ref, c_ref, kap_ref, ds_ref = refs[-5:]
    zr = pl.program_id(2)
    nc = gate_ref.shape[2]
    dk, dv = q_ref.shape[2], v_ref.shape[2] // r
    W = r * dv
    cd, f32 = q_ref.dtype, jnp.float32
    exact = cd == f32

    @pl.when(zr == 0)
    def _():
        dstate[...] = jnp.zeros(dstate.shape, f32)

    # ---- the forward's chunks again, from the kept states
    pre = _prepare(q_ref, k_ref, gate_ref, Q, r, norms and norms[0])
    q, k, k2, M, P, b_c, D_lo, D_up = (pre[a] for a in (
        "q", "k", "k2", "M", "P", "b_c", "D_lo", "D_up"))
    Pc = P.astype(cd)
    hd = _end_blocks(pre["tiles"], pre["cols"], Q, r, dv)
    kf = k.astype(f32)
    kw, qw = kf, q.astype(f32)
    if r > 1:  # a head's copy on its own lanes
        kw, qw = (jnp.concatenate([a] * r, axis=2) for a in (kw, qw))
    S0c = s_ref[0, :, 0]  # [chunks, dk, r · dv]
    kq = jnp.concatenate([k, q], axis=1)
    kqS = _dots("nqk,nkw->nqw", kq, S0c, exact)
    kS, qS = kqS[:, :Q], kqS[:, Q:]
    Rv = v_ref[0].reshape(nc, Q, W).astype(f32) - hd["e"] * kS  # R = β ⊙ Rv
    DW = _dots("nij,njk->nik", M, jnp.concatenate(
        [_blocks((hd["b"] * Rv).astype(cd), r, dv),
         _blocks((hd["b"] * hd["e"] * kw).astype(cd), r, dv)], axis=2),
        exact)  # [chunks, Q, 2 W]: Δ | W
    deltas = _blocks(DW[..., :W].astype(cd), r, dv)
    Ws = _blocks(DW[..., W:].astype(cd), r, dv)
    PWD = _dots("nij,njk->nik", Pc,
                jnp.concatenate([Ws, deltas], axis=2) if norms else Ws,
                exact)  # P W (| P Δ)
    do = do_ref[0].reshape(nc, Q, W).astype(f32)
    if norms:  # do is d y: through the gate and the norm to d o
        z_ref, w_ref = refs[6:8]
        dz_ref, dw_ref = refs[n_in + 4:n_in + 6]

        @pl.when(zr == 0)
        def _():
            dw_ref[...] = jnp.zeros(dw_ref.shape, f32)

        z, w = z_ref[0].reshape(nc, Q, W).astype(f32), w_ref[...]
        n, rstd = _rms_parts(hd["e"] * qS + PWD[..., W:], norms[1], dv)
        sig = jax.nn.sigmoid(z)
        dz_ref[0] = (do * (n * w).astype(cd).astype(f32)
                     * (sig * (1.0 + z * (1.0 - sig)))
                     ).astype(dz_ref.dtype).reshape(nc * Q, W)
        dy = (do * (z * sig)).astype(cd).astype(f32)  # as y was rounded
        dw_ref[0, 0] += jnp.broadcast_to(
            jnp.sum(jnp.sum(dy * n, axis=0), axis=0, keepdims=True),
            dw_ref.shape[2:])
        dn = dy * w
        do = rstd * (dn - n * _head_means(dn * n, dv))
    doc = do.astype(cd)
    # ---- what ties a chunk's dS₀ to its dS₁: Gᵀ, κ and q̃ᵀ do
    GT = _dots("nqw,nqk->nwk", (hd["t"] * DW[..., W:]).astype(cd), k, exact)
    qe = (hd["e"] * qw - PWD[..., :W]).astype(cd)  # q̃
    for h in range(r):
        at = slice(h * dv, (h + 1) * dv)
        gt_ref[:, h] = GT[:, at, :].astype(cd)
        c_ref[:, :, at] = _dots("nqk,nqw->nkw", qe[..., at], doc[..., at],
                                exact)
    kap_ref[...] = jnp.broadcast_to(hd["kappa"], kap_ref.shape)

    def chunk(ci, carry):  # dS₀ = κ dS₁ − Gᵀ dS₁ + q̃ᵀ do
        c = nc - 1 - ci
        dS1 = dstate[...]
        ds_ref[c] = dS1
        dS1c = dS1.astype(cd)
        outs = [_dot(gt_ref[c, h], dS1c[:, h * dv:(h + 1) * dv], None, exact)
                for h in range(r)]
        out = jnp.concatenate(outs, axis=1) if r > 1 else outs[0]
        dstate[...] = kap_ref[c, :1, :] * dS1 + c_ref[c] - out
        return carry

    jax.lax.fori_loop(0, nc, chunk, 0)
    # ---- Δ enters o through P and the leaving state through t ⊙ k
    dS1 = ds_ref[...]  # [chunks, dk, r · dv]: d of the state LEAVING each
    dS1c = dS1.astype(cd)
    d_delta = (_diagonal(_dots("nij,niw->njw", Pc, doc, exact), r, dv)
               + hd["t"] * _dots("nqk,nkw->nqw", k, dS1c, exact))
    dP = _dots("nqw,njw->nqj", doc, deltas, exact)  # [chunks, Q, 2 Q]
    dR = _diagonal(_dots("nij,niw->njw", M, d_delta.astype(cd), exact),
                   r, dv)
    dA = -_dots("nqw,njw->nqj", dR.astype(cd), deltas, exact)
    Yp = dA * pre["kk"] * D_lo  # d β's part through A, a row sum
    Zs = dP * P + b_c * Yp  # d (c_i − c_j) of both decay blocks
    dqk, dkk = (dP * D_up).astype(cd), (b_c * dA * D_lo).astype(cd)
    dv_ref[0] = (hd["b"] * dR).astype(dv_ref.dtype).reshape(nc * Q, W)
    g_kS = (-(hd["b"] * hd["e"]) * dR).astype(cd)
    g_qS = (hd["e"] * do).astype(cd)
    dtk = _dots("njw,nkw->njk", deltas, dS1c, exact)  # Δ_h · dS₁_hᵀ
    dtks = [dtk[:, h * Q:(h + 1) * Q] for h in range(r)]
    # the products against a pair sum over its heads by themselves; k's
    # rows above q's, as their right factors are shared
    pair = jnp.concatenate([dkk, dqk], axis=1)  # [chunks, 2 Q, 2 Q]
    halves = _dots("nij,nik->njk", pair, kq, exact)
    both = (_dots("nqw,nkw->nqk", jnp.concatenate([g_kS, g_qS], axis=1), S0c,
                  exact) + _dots("nqj,njk->nqk", pair, k2, exact))
    dqry, dkey = both[:, Q:], both[:, :Q] + halves[:, :Q] + halves[:, Q:]
    for h in range(r):
        dkey = dkey + hd["t_c"][h] * dtks[h]
    if norms:  # d x = (d x̂ − u (u · d x̂)) · rsqrt(Σ x² + eps)
        uq, rq, uk, rk = pre["l2"]
        dqry, dkey = (
            rs * (d - u * jnp.sum(u * d, axis=2, keepdims=True))
            for d, u, rs in ((dqry, uq, rq * dk ** -0.5), (dkey, uk, rk)))
    dq_ref[0] = dqry.astype(dq_ref.dtype).reshape(nc * Q, dk)
    dk_ref[0] = dkey.astype(dk_ref.dtype).reshape(nc * Q, dk)
    # ---- sums over a token's channels, tokens in the lanes: the lanes of
    #      :func:`_lane_sums`' operand [Zs | Yp | d e | d t | d β] (a pair,
    #      a pair, three of a head's dv lanes) that make row 5 h + 0 Σ_j
    #      Zs, 1 Σ_j Yp, 2 d e, 3 d t, 4 d β through R
    ranges = [(first + h * width, width) for h in range(r)
              for first, width in ((0, Q), (2 * Q, Q), (4 * Q, dv),
                                   (4 * Q + W, dv), (4 * Q + 2 * W, dv))]
    sums = _lane_sums(jnp.concatenate(
        [Zs, Yp, do * qS - hd["b"] * dR * kS,
         jnp.concatenate([kf * d for d in dtks], axis=2) if r > 1
         else kf * dtks[0], dR * Rv], axis=2), ranges, exact)
    down = jnp.sum(Zs, axis=1, keepdims=True)  # [chunks, 1, 2 Q]
    held = jnp.sum(dS1 * S0c.astype(f32), axis=1, keepdims=True)
    sub = jax.lax.broadcasted_iota(jnp.int32, (nc, SUBLANE, Q), 1)
    last = _lane((nc, 1, Q)) == Q - 1
    out = jnp.zeros((nc, SUBLANE, Q), f32)
    for h in range(r):
        at = 5 * h
        d_kappa = jnp.sum(held[..., h * dv:(h + 1) * dv], axis=2,
                          keepdims=True)  # [chunks, 1, 1]
        dt_t = sums[:, at + 3:at + 4] * hd["t_r"][h]
        d_last = (jnp.sum(dt_t, axis=2, keepdims=True)
                  + d_kappa * hd["kappa"][..., h * dv:h * dv + 1])
        d_c = (sums[:, at:at + 1] - down[..., h * Q:(h + 1) * Q]
               + sums[:, at + 2:at + 3] * hd["e_r"][h] - dt_t)
        d_c = d_c + jnp.where(last, d_last, 0.0)
        out = jnp.where(sub == h, d_c, out)
        out = jnp.where(sub == r + h,
                        sums[:, at + 1:at + 2] + sums[:, at + 4:at + 5], out)
    dgate_ref[0, 0] = jnp.concatenate(
        [out, jnp.zeros((nc, SUBLANE, LANE - Q), f32)], axis=2)


def _params(interpret: bool):
    kw = dict(interpret=interpret)
    if not interpret:
        kw["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT)
    return kw


def gate_tiles(g, beta, seg, chunk: int, k_heads: int):
    """g, beta [R, T, H] float32 and seg [R, T] (T whole chunks) -> [R, G,
    chunks, 8, 128] float32, a chunk's tile a key head: row 0 the
    cumulated g of the key head's r value heads, head h on lanes h · Q ..;
    1 their β the same way; 2 the segment ids, twice; 3, 4 each head's
    cumulated g at the chunk's end on every lane; 5 the second head's
    cumulated g on the first lanes; 6, 7 the document the row is in
    before the chunk (−1 in front of the first) and at its end, on every
    lane."""
    R, T, H = g.shape
    Q, Z, G = chunk, T // chunk, k_heads
    r = H // G
    f32 = jnp.float32

    def heads(a):  # [R, T, H] -> [R, G, Z, r, Q]
        return a.astype(f32).reshape(R, Z, Q, G, r).transpose(0, 3, 1, 4, 2)

    def lanes(a):  # [R, G, Z, rows, w] -> [R, G, Z, rows, 128], zeros behind
        return jnp.pad(a, ((0, 0),) * 4 + ((0, LANE - a.shape[-1]),))

    def every(a, rows=1):  # [R, Z] -> [R, G, Z, rows, 128], on every lane
        return jnp.broadcast_to(a[:, None, :, None, None],
                                (R, G, Z, rows, LANE))

    cs = heads(chunk_cumsum(g.astype(f32), Q))
    segz = seg.astype(jnp.int32).reshape(R, Z, Q).astype(f32)
    last = segz[:, :, -1]
    prev = jnp.pad(last, ((0, 0), (1, 0)), constant_values=-1.0)[:, :Z]
    return jnp.concatenate(
        [lanes(cs.reshape(R, G, Z, 1, r * Q)),
         lanes(heads(beta).reshape(R, G, Z, 1, r * Q)),
         lanes(jnp.broadcast_to(jnp.tile(segz, (1, 1, 2))[:, None, :, None],
                                (R, G, Z, 1, 2 * Q))),
         jnp.broadcast_to(cs[..., -1:], (R, G, Z, r, LANE)),
         jnp.zeros((R, G, Z, _C_SECOND - _C_END - r, LANE), f32),
         lanes(cs[:, :, :, 1:2]) if r > 1 else every(last * 0.0),
         every(prev), every(last)], axis=3)


def _dims(q, v, chunk: int, backward: bool = False):
    R, T, G, dk = q.shape
    H, dv = v.shape[2:]
    Z = T // chunk
    return R, T, G, H, dk, dv, H // G, Z, chunks_per_step(Z, backward)


def _specs(dims, Q: int, reverse: bool):
    R, T, G, H, dk, dv, r, Z, nc = dims
    steps = Z // nc

    def at(z):
        return steps - 1 - z if reverse else z

    key = pl.BlockSpec((1, nc * Q, dk), lambda b, j, z, *_: (b, at(z), j))
    val = pl.BlockSpec((1, nc * Q, r * dv), lambda b, j, z, *_: (b, at(z), j))
    gate = pl.BlockSpec((1, 1, nc, SUBLANE, LANE),
                        lambda b, j, z, *_: (b, j, at(z), 0, 0))
    st = pl.BlockSpec((1, nc, 1, dk, r * dv),
                      lambda b, j, z, *_: (b, at(z), j, 0, 0))
    return key, val, gate, st, steps


def _norm_operands(norms, dims):
    """``norms`` = (z [R, T, H · dv], the gated norm's weight [dv], the l2
    norm's epsilon, the RMS norm's) -> (z, the weight a key head's value
    heads wide [1, r · dv] float32, its BlockSpec, the two epsilons)."""
    z, w, l2_eps, rms_eps = norms
    r = dims[6]
    w = jnp.tile(w.astype(jnp.float32), r)[None]
    spec = pl.BlockSpec(w.shape, lambda b, j, z, *_: (0, 0))
    return z, w, spec, (float(l2_eps), float(rms_eps))


def rule_fwd(q, k, v, g, beta, seg, chunk: int, keep: bool = False,
             interpret: bool = False, norms=None):
    """q, k [R, T, G, dk] and v [R, T, H, dv] in the compute dtype; g, beta
    [R, T, H] float32; seg [R, T] int; T a whole number of chunks. Returns
    (o [R, T, H, dv] float32, the state entering each chunk [R, chunks, G,
    dk, r · dv] in the compute dtype or, without ``keep``, None). With
    ``norms`` (:func:`_norm_operands`) q and k are the mixer's RAW ones and
    the first result is its ``y`` [R, T, H · dv] in the compute dtype."""
    dims = _dims(q, v, chunk)
    R, T, G, H, dk, dv, r, Z, nc = dims
    key, val, gate, st, steps = _specs(dims, chunk, reverse=False)
    tile = gate_tiles(g, beta, seg, chunk, G)
    f32 = jnp.float32
    more, more_specs, eps = (), [], None
    if norms is not None:
        z, w, w_spec, eps = _norm_operands(norms, dims)
        more, more_specs = (z, w), [val, w_spec]
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, Q=chunk, r=r, keep=keep, norms=eps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=(R, G, steps),
            in_specs=[key, key, val, gate] + more_specs,
            out_specs=[val] + ([st] if keep else []),
            scratch_shapes=[pltpu.VMEM((dk, r * dv), f32),
                            pltpu.VMEM((nc, r, dk + chunk, dk), q.dtype),
                            pltpu.VMEM((nc, dk, r * dv), f32),
                            pltpu.VMEM((nc, chunk, r * dv), f32),
                            pltpu.VMEM((nc, SUBLANE, r * dv), f32)]),
        out_shape=[jax.ShapeDtypeStruct((R, T, H * dv),
                                        f32 if eps is None else q.dtype)] + (
            [jax.ShapeDtypeStruct((R, Z, G, dk, r * dv), q.dtype)]
            if keep else []),
        name=FWD_NAME, **_params(interpret),
    )(q.reshape(R, T, G * dk), k.reshape(R, T, G * dk),
      v.reshape(R, T, H * dv), tile, *more)
    o = out[0] if norms is not None else out[0].reshape(R, T, H, dv)
    return o, (out[1] if keep else None)


def rule_bwd(q, k, v, g, beta, seg, states, do, chunk: int,
             interpret: bool = False, norms=None):
    """Gradients (dq, dk, dv in the compute dtype; dg, dbeta [R, T, H]
    float32) from the forward's operands, its kept states and do. With
    ``norms`` (as :func:`rule_fwd`'s) ``do`` is the cotangent of ``y``, dq
    and dk are those of the raw q and k, and two more follow: dz in the
    compute dtype and the norm's weight's gradient [dv] float32."""
    dims = _dims(q, v, chunk, backward=True)
    R, T, G, H, dk, dv, r, Z, nc = dims
    key, val, gate, st, steps = _specs(dims, chunk, reverse=True)
    tile = gate_tiles(g, beta, seg, chunk, G)
    cd = q.dtype
    more, more_specs, more_out, more_shapes, eps = (), [], [], [], None
    if norms is not None:
        z, w, w_spec, eps = _norm_operands(norms, dims)
        more, more_specs = (z, w), [val, w_spec]
        # the weight's gradient: a block a (row, key head), summed over
        # the row's steps where it stays
        more_out = [val, pl.BlockSpec((1, 1, SUBLANE, r * dv),
                                      lambda b, j, z, *_: (b, j, 0, 0))]
        more_shapes = [
            jax.ShapeDtypeStruct((R, T, H * dv), cd),
            jax.ShapeDtypeStruct((R, G, SUBLANE, r * dv), jnp.float32)]
    dq, dkey, dval, dgate, *rest = pl.pallas_call(
        functools.partial(_bwd_kernel, Q=chunk, r=r, norms=eps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=(R, G, steps),
            in_specs=[key, key, val, gate, st, val] + more_specs,
            out_specs=[key, key, val, gate] + more_out,
            scratch_shapes=[pltpu.VMEM((dk, r * dv), jnp.float32),
                            pltpu.VMEM((nc, r, dv, dk), cd),
                            pltpu.VMEM((nc, dk, r * dv), jnp.float32),
                            pltpu.VMEM((nc, SUBLANE, r * dv), jnp.float32),
                            pltpu.VMEM((nc, dk, r * dv), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((R, T, G * dk), cd),
                   jax.ShapeDtypeStruct((R, T, G * dk), cd),
                   jax.ShapeDtypeStruct((R, T, H * dv), cd),
                   jax.ShapeDtypeStruct(tile.shape, jnp.float32)
                   ] + more_shapes,
        name=BWD_NAME, **_params(interpret),
    )(q.reshape(R, T, G * dk), k.reshape(R, T, G * dk),
      v.reshape(R, T, H * dv), tile, states, do.reshape(R, T, H * dv), *more)

    def tokens(a):  # [R, G, Z, r, Q] -> [R, Z, Q, H]
        return a.transpose(0, 2, 4, 1, 3).reshape(R, Z, chunk, H)

    dcs = tokens(dgate[:, :, :, :r, :chunk])
    # the cumulated sum's transpose: a token's own and every later one of
    # its chunk, as the chunk's total less the sum before it
    dg = (jnp.sum(dcs, axis=2, keepdims=True) - jnp.cumsum(dcs, axis=2)
          + dcs).reshape(R, T, H)
    dbeta = tokens(dgate[:, :, :, r:2 * r, :chunk]).reshape(R, T, H)
    grads = (dq.reshape(q.shape), dkey.reshape(k.shape),
             dval.reshape(v.shape), dg, dbeta)
    if norms is not None:
        dz, dw = rest
        grads += (dz, jnp.sum(dw[:, :, 0].reshape(R * G * r, dv), axis=0))
    return grads
