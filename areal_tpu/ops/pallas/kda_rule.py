"""TPU chunked delta rule with a decay a key CHANNEL (Kimi Delta Attention)
for packed segment batches.

    S ← Diag(e^{g_t}) S;  δ_t = β_t (v_t − Sᵀ k_t);  S ← S + k_t δ_tᵀ;  o_t = Sᵀ q_t

(S [dk, dv] float32 a head, zero before a document's first token; ``g_t``
[dk] <= 0) — the chunked algorithm of ``models/gdn.gated_delta_rule`` at a
decay a channel, one grid step a (row, head, run of chunks), the chunk axis
innermost and sequential. β never enters the kernels: the caller hands
them ``kb = β ⊙ k`` and ``vb = β ⊙ v`` beside ``k``, and β's gradient is
XLA's through those two products. With ``c`` the cumulated ``g`` inside a
chunk [Q, dk] and ``S₀`` the state entering it:

    A_ij = Σ_d kb_id k_jd e^{c_id − c_jd}    j < i, one document; else exactly 0
    M    = (I + A)^-1
    Δ    = M · (vb − (kb ⊙ e) S₀)            e_i = e^{c_i} on the tokens still in
                                              the document the row entered in
    o    = (q ⊙ e) S₀ + P Δ                   P_ij = Σ_d q_id k_jd e^{c_id − c_jd}, j <= i
    S₁   = Diag(κ) S₀ + (t ⊙ k)ᵀ Δ            t_j = e^{c_Q − c_j} on the tokens of the
                                              document the chunk ends in, κ = e^{c_Q}
                                              where that is the entering one

**A step works in two phases**, as ``gated_delta_rule.py``'s does. With ``U =
M vb`` and ``W = M (kb ⊙ e)`` (so that ``Δ = U − W S₀``) the state enters
a chunk through four blocks only:

    G = (t ⊙ k)ᵀ W [dk, dk]    C = (t ⊙ k)ᵀ U [dk, dv]
    q̃ = q ⊙ e − P W [Q, dk]    P U [Q, dv]
    o = q̃ S₀ + P U             S₁ = (Diag(κ) − G) S₀ + C

FIRST everything no state enters, for ALL the step's chunks at once
(:func:`_blocks`: [chunks, ., .] arrays, one batched product an operation,
so the chunks' chains of dependent products — the inverse's above all — run
side by side, and the kernel's text does not grow with the chunks a step
holds), into VMEM scratches; THEN the states' chain, a ``fori_loop`` over
the chunks with ONE product a chunk, ``[G; q̃] · S₀``, the state a VMEM
scratch that rides the chunk axis. 8 chunks a step at ANY row: a row's last
step may be short (118 chunks are 14 steps and 6 chunks), and what its
blocks hold past the row's end is replaced by zeros before anything reads
it (:func:`_real`; the mask tiles are built to whole steps, and writes
past the end are dropped) — no padded copy of an operand.

**The decay does not leave the product** as a scalar factor does, and
``(k_i ⊙ e^{c_i})·(k_j ⊙ e^{−c_j})`` would take a POSITIVE exponent. Every
exponent here is that of a non-positive difference (``ssm._masked_exp``'s
contract; no clamp on ``g``), which takes a reference INSIDE the chunk,
BETWEEN the two tokens. The pairs j < i of a chunk fall into log₂ Q levels
by the highest bit in which i and j differ: at level w (1, 2, .. Q / 2) j
is in the first half of a block of 2w tokens and i in its second, and with
``c_mid`` the second half's first row, ``e^{c_i − c_j} = e^{c_i − c_mid} ·
e^{c_mid − c_j}``, both non-positive. ONE array a level serves both sides,
``F = e^{−|c − c_mid|}`` (:func:`_halvings`), and ONE product a level,
``[kb ⊙ F; q ⊙ F] · (k ⊙ F)ᵀ``, makes the level's part of A and of P
(:func:`_decay_blocks`): six products and six exponentials of [Q, dk] a
chunk. (PR 63's first build took sub-blocks of ``SUB`` = 16 tokens — as
the XLA form ``models/kda._rule_xla`` does, which reads the constant here
— with the sub-blocks on the diagonal made element by element, sixteen
exponentials and thirty-two lane reductions of [Q, dk] a chunk: a quarter
of the batched forward and two fifths of its backward; PERF.md §6, PR 64.)

**One text of the chunk algebra.** Float32 gates, ``A``, ``M`` and state,
matmul operands in the compute dtype and every sum float32; the state
enters every product rounded to the compute dtype. The backward kernel
works in the same phases, the chunks reversed and ``dS`` its carry: FIRST
:func:`_blocks` again for all the step's chunks at once, under ``jax.vjp``
INSIDE the kernel body — the two passes cannot disagree on a mask, a
reference or a rounding point —, then ``Gᵀ``, ``q̃ᵀ do`` and κ into
scratches; THEN the chain ``dS₀ = κ ⊙ dS₁ − Gᵀ dS₁ + q̃ᵀ do`` from the
step's last chunk to its first, one product a chunk, each chunk's ``dS₁``
left in a scratch; LAST the cotangents of the four blocks and of κ from
those ``dS₁`` and the kept states (``dG = −dS₁ S₀ᵀ``, ``dq̃ = do S₀ᵀ``, ``dC
= dS₁``, ``d(P U) = do``, ``dκ`` the diagonal of ``dS₁ S₀ᵀ``), pulled back to
every operand for all the chunks at once. Three cotangents are written by
hand, each beside the forward it transposes: the inverse's (``−Mᵀ M̄ Mᵀ``),
a product's (the cotangent rounded to the compute dtype before it
multiplies: :func:`_product`) and the decay blocks' (the same levels and
factors; the decays' own is ``kb ⊙ dkb + q ⊙ dq − k ⊙ dk`` over the terms
that pass an exponent, so no reference row needs a gradient).

The kernels' device ops are named ``kda_rule_fwd`` / ``kda_rule_bwd`` under
the caller's scope (not jitted by themselves: the benchmark reads the rule
by its scope ``kda_rule``). :func:`step_counts` is the trace-time count of
the chunks a step either kernel was built at. CPU/testing:
``interpret=True``; tests/test_tpu_compile.py compiles them for a
described v5e.
"""

from __future__ import annotations

import collections
import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from areal_tpu.ops.pallas.gated_delta_rule import (  # noqa: F401
    LANE,
    SUBLANE,
    _dot,
    _dots,
    _inverses,
    _params,
    fits_device,
)

FWD_NAME, BWD_NAME = "kda_rule_fwd", "kda_rule_bwd"
# Tokens a sub-block of the XLA form (``models/kda._rule_xla``; the kernels
# halve instead: the module's docstring): the chunk's 64 are four.
SUB = 16
# Chunks a grid step (a row need not hold a whole number of steps:
# :func:`steps_of`). tools/kda_rule_sweep.py --steps, bf16, 8 heads, forward /
# forward + backward with the other kernel at 8 (my chip runs, PR 64): 1 x
# 8,192 reads 1.557 / 3.703 ms at 4, 1.258 / 3.253 at 8, 1.098 / 3.075 at 16; 2
# x 7,552 (118 chunks a row: a short last step) 2.891 / 7.569, 2.323 / 6.726,
# 2.096 / 6.514. 16 holds twice the VMEM in the first phase (the backward's
# vjp did not fit at 16 before the decay blocks were halved) and pads 118
# chunks to 128 where 8 pads to 120.
CHUNKS_PER_STEP = 8
# The backward's own (all three of its phases hold a step's chunks).
BWD_CHUNKS_PER_STEP = 8
# The rows of a chunk's mask tile (:func:`mask_tiles`).
_SEG, _ENTERS, _TO_END, _KEEPS = 0, 1, 2, 3
_STEPS: collections.Counter = collections.Counter()


def supported(chunk: int, heads: int, dk: int, dv: int, dtype) -> bool:
    """What the kernels take: chunks of 64, heads of one lane tile, float32
    or bfloat16."""
    return (chunk == 64 and dk == LANE and dv == LANE and heads >= 1
            and jnp.dtype(dtype) in (jnp.dtype(jnp.float32),
                                     jnp.dtype(jnp.bfloat16)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _unit_inverses(A, exact: bool):
    """``(I + A)^-1`` of a step's strictly lower-triangular float32 [n, Q,
    Q], by the delta rule kernel's blocks (``gated_delta_rule._inverses``
    takes PAIRS along the lanes: two CHUNKS a pair, chunk i beside chunk i +
    n / 2; a chunk alone beside zeros)."""
    n, Q, _ = A.shape
    if n == 1:
        pair = jnp.concatenate([A, jnp.zeros_like(A)], axis=2)
        return _inverses(pair, exact)[:, :, :Q]
    M = _inverses(jnp.concatenate([A[:n // 2], A[n // 2:]], axis=2), exact)
    return jnp.concatenate([M[:, :, :Q], M[:, :, Q:]], axis=0)


def _unit_inverses_fwd(A, exact):
    M = _unit_inverses(A, exact)
    return M, M


def _unit_inverses_bwd(exact, M, ct):
    cd = jnp.float32 if exact else jnp.bfloat16
    Mc = M.astype(cd)
    left = _dots("nji,njk->nik", Mc, ct.astype(cd), exact)  # Mᵀ M̄
    return (-_dots("nij,nkj->nik", left.astype(cd), Mc, exact),)


_unit_inverses.defvjp(_unit_inverses_fwd, _unit_inverses_bwd)


def _iota(shape, axis: int):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _own(shape):
    """Where [n, m, m] holds its diagonals."""
    return _iota(shape, 1) == _iota(shape, 2)


def _to(target: str, s1: str, x1, s2: str, x2, exact: bool):
    """``x1 · x2`` [``target``], the factor that holds the result's rows
    on the left (no transpose of a result)."""
    if target[1] in s2:
        s1, x1, s2, x2 = s2, x2, s1, x1
    return _dots(f"{s1},{s2}->{target}", x1, x2, exact)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 3))
def _product(spec: str, a, b, exact: bool):
    """``_dots`` whose cotangent is rounded to the factors' dtype before it
    multiplies (the module's rounding points hold in the backward: the
    transpose of a product with a float32 sum would multiply a float32
    cotangent by a compute-dtype factor at float32's cost)."""
    return _dots(spec, a, b, exact)


def _product_fwd(spec, a, b, exact):
    return _dots(spec, a, b, exact), (a, b)


def _product_bwd(spec, exact, res, ct):
    a, b = res
    ins, out = spec.split("->")
    sa, sb = ins.split(",")
    ct = ct.astype(a.dtype)
    return (_to(sa, out, ct, sb, b, exact).astype(a.dtype),
            _to(sb, out, ct, sa, a, exact).astype(b.dtype))


_product.defvjp(_product_fwd, _product_bwd)


def _cumulate(g):
    """g [n, Q, dk] float32 -> its inclusive sum along a chunk's tokens (a
    product against 0/1 at float32's precision)."""
    n, Q, _ = g.shape
    tri = (_iota((n, Q, Q), 1) >= _iota((n, Q, Q), 2)).astype(jnp.float32)
    return _dots("nij,njk->nik", tri, g, True)


def _row_of(x, r: int):
    """[n, Q, dk] -> each chunk's row r [n, 1, dk] (a masked sum: no slice
    of a differentiated array)."""
    return jnp.sum(jnp.where(_iota(x.shape, 1) == r, x, 0.0), axis=1,
                   keepdims=True)


def _halvings(c):
    """c [n, Q, dk], a chunk's cumulated decay -> [(w, F)] for w = 1, 2, 4,
    .. Q / 2 (the module's docstring): ``F = e^{−|c − c_mid|}`` [n, Q, dk],
    ``c_mid`` the FIRST row of the second half of a row's block of 2w
    tokens. A row of the second half reads ``e^{c_i − c_mid}``, one of the
    first ``e^{c_mid − c_j}``: ``j < mid <= i``, both non-positive. Rows
    move by rotations of the sublanes (no row of the wrap is taken)."""
    Q = c.shape[1]
    row = _iota(c.shape, 1)
    first = c  # c at the first row of a row's block of w
    out = []
    w = 1
    while w < Q:
        second = (row & w) != 0
        mid = jnp.where(second, first, pltpu.roll(first, Q - w, 1))
        out.append((w, jnp.exp(-jnp.abs(c - mid))))
        first = jnp.where(second, pltpu.roll(first, w, 1), first)
        w *= 2
    return out


def _level(shape, Q: int, w: int):
    """Where ``shape`` [n, Q or 2 Q (A's rows above P's), Q] holds the pairs
    (i, j) of level w: j in the first half of a block of 2w, i in its
    second."""
    i, j = _iota(shape, 1) & (Q - 1), _iota(shape, 2)
    return ((i ^ j) >= w) & ((i ^ j) < 2 * w) & (i > j)


def _factors(F, qf, kf, kbf, cd):
    """A level's two factors in the compute dtype: [kb ⊙ F; q ⊙ F] [n, 2 Q,
    dk] and k ⊙ F."""
    return (jnp.concatenate([kbf * F, qf * F], axis=1).astype(cd),
            (kf * F).astype(cd))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _decay_blocks(c, qf, kf, kbf, cd, exact: bool):
    """(A, P) [n, Q, Q] float32 before the documents' mask: ``Σ_d x_id k_jd
    e^{c_id − c_jd}`` for j < i (x = kb, q) and P's diagonal ``q_i · k_i``
    — ONE product a level of :func:`_halvings`, kept where the level's
    pairs are. q, k, kb arrive as float32 (their cotangents leave as
    float32 and meet the operands' other uses before ONE rounding); the
    factors are rounded to ``cd``."""
    n, Q, _ = c.shape
    f32 = jnp.float32
    AP = jnp.zeros((n, 2 * Q, Q), f32)
    for w, F in _halvings(c):
        left, right = _factors(F, qf, kf, kbf, cd)
        AP = jnp.where(_level(AP.shape, Q, w),
                       _dots("nik,njk->nij", left, right, exact), AP)
    return AP[:, :Q], jnp.where(
        _own((n, Q, Q)), jnp.sum(qf * kf, axis=2, keepdims=True), AP[:, Q:])


def _decay_blocks_fwd(c, qf, kf, kbf, cd, exact):
    return _decay_blocks(c, qf, kf, kbf, cd, exact), (c, qf, kf, kbf)


def _decay_blocks_bwd(cd, exact, res, cts):
    """The same levels, the same factors: a level's cotangents are two
    products, and the decays' is ``kb ⊙ dkb + q ⊙ dq − k ⊙ dk`` over the
    terms that pass an exponent (every one but P's diagonal)."""
    c, qf, kf, kbf = res
    n, Q, _ = c.shape
    f32 = jnp.float32
    dAP = jnp.concatenate(cts, axis=1)
    d_left, d_right = jnp.zeros((n, 2 * Q) + c.shape[2:], f32), jnp.zeros(
        c.shape, f32)
    for w, F in _halvings(c):
        left, right = _factors(F, qf, kf, kbf, cd)
        D = jnp.where(_level(dAP.shape, Q, w), dAP, 0.0).astype(cd)
        d_left = d_left + jnp.concatenate([F, F], axis=1) * _dots(
            "nij,njk->nik", D, right, exact)
        d_right = d_right + F * _dots("nij,nik->njk", D, left, exact)
    dkb, dq = d_left[:, :Q], d_left[:, Q:]
    own = jnp.sum(jnp.where(_own((n, Q, Q)), cts[1], 0.0), axis=2,
                  keepdims=True)
    return (kbf * dkb + qf * dq - kf * d_right, dq + own * kf,
            d_right + own * qf, dkb)


_decay_blocks.defvjp(_decay_blocks_fwd, _decay_blocks_bwd)


def _blocks(q, k, kb, vb, g, tiles, exact: bool):
    """Everything of a step's chunks that no state enters (the module's
    docstring), for all of them at once: q, k, kb [n, Q, dk] and vb [n, Q,
    dv] in the compute dtype, g [n, Q, dk] float32, ``tiles`` the chunks'
    mask tiles [n, 8, 128] -> (G [n, dk, dk], C [n, dk, dv], q̃ [n, Q, dk],
    PU [n, Q, dv], κ [n, 1, dk]), float32. Differentiable in everything but
    the tiles, with no slice of a differentiated array (rows are taken by
    masked sums; products that share a left factor are made apart)."""
    n, Q, dk = q.shape
    cd, f32 = q.dtype, jnp.float32
    cols = jnp.swapaxes(tiles, 1, 2)  # the same quantities down the sublanes
    seg_r, seg_c = tiles[:, _SEG:_SEG + 1, :Q], cols[:, :Q, _SEG:_SEG + 1]
    ent_c = cols[:, :Q, _ENTERS:_ENTERS + 1]
    end_c = cols[:, :Q, _TO_END:_TO_END + 1]
    keeps = tiles[:, _KEEPS:_KEEPS + 1, :dk]
    ii, jj = _iota((n, Q, Q), 1), _iota((n, Q, Q), 2)
    same = seg_c == seg_r
    c = _cumulate(g)
    qf, kf, kbf = (a.astype(f32) for a in (q, k, kb))
    A, P = _decay_blocks(c, qf, kf, kbf, cd, exact)
    A = jnp.where(same & (ii > jj), A, 0.0)
    P = jnp.where(same & (ii >= jj), P, 0.0).astype(cd)
    M = _unit_inverses(A, exact).astype(cd)
    # ---- what ties a chunk to the states (Δ = U − W S₀)
    c_end = _row_of(c, Q - 1)
    e = jnp.where(ent_c > 0, jnp.exp(c), 0.0)
    t = jnp.where(end_c > 0, jnp.exp(c_end - c), 0.0)
    kappa = jnp.where(keeps > 0, jnp.exp(c_end), 0.0)
    U = _product("nij,njk->nik", M, vb, exact)
    W = _product("nij,njk->nik", M, (kbf * e).astype(cd), exact)
    Uc, Wc, kt = U.astype(cd), W.astype(cd), (kf * t).astype(cd)
    G = _product("nqk,nqw->nkw", kt, Wc, exact)
    C = _product("nqk,nqw->nkw", kt, Uc, exact)
    qt = qf * e - _product("nij,njk->nik", P, Wc, exact)
    return G, C, qt, _product("nij,njk->nik", P, Uc, exact), kappa


def mask_tiles(seg, chunk: int, chunks: int = 0):
    """seg [R, T] (T whole chunks) -> [R, chunks, 8, 128] float32, a
    chunk's tile: row 0 the segment ids on the first ``chunk`` lanes; 1
    the tokens (1.0) still in the document the row was in before the chunk
    (none in the row's first chunk); 2 those of the document the chunk
    ends in; 3 on every lane whether those two documents are one. With
    ``chunks`` past the row's own (whole grid steps), chunks of segment 0
    behind it."""
    R, T = seg.shape
    Q = chunk
    Z = max(chunks, T // Q)
    f32 = jnp.float32
    segz = jnp.pad(seg.astype(jnp.int32), ((0, 0), (0, Z * Q - T))
                   ).reshape(R, Z, Q)
    last = segz[:, :, -1]
    prev = jnp.pad(last, ((0, 0), (1, 0)), constant_values=-1)[:, :Z]

    def lanes(a):  # [R, Z, Q] -> [R, Z, 1, 128]
        return jnp.pad(a.astype(f32), ((0, 0), (0, 0), (0, LANE - Q))
                       )[:, :, None]

    keeps = jnp.broadcast_to((last == prev).astype(f32)[:, :, None, None],
                             (R, Z, 1, LANE))
    return jnp.concatenate(
        [lanes(segz), lanes(segz == prev[..., None]),
         lanes(segz == last[..., None]), keeps,
         jnp.zeros((R, Z, SUBLANE - 4, LANE), f32)], axis=2)


def _chunks(ref, nc: int):
    """A step's block of tokens [1, n · Q, w] -> [n, Q, w]."""
    x = ref[0]
    return x.reshape((nc, x.shape[0] // nc) + x.shape[1:])


def _real(xs, step, Z: int):
    """A row's LAST step may reach past the row's ``Z`` chunks: what the
    blocks ([n, ., .] each) hold there is not the row's (nor anything's),
    and is replaced by zeros before any exponent or product sees it (the
    mask tiles there are segment 0)."""
    nc = xs[0].shape[0]
    if Z % nc == 0:
        return xs
    real = step * nc + _iota((nc, 1, 1), 0) < Z
    return [jnp.where(real, x, jnp.zeros_like(x)) for x in xs]


def _walk(nc: int, chunk):
    """The states' chain: ``chunk(i, 0)`` a chunk of the step, in turn."""
    jax.lax.fori_loop(0, nc, chunk, 0)


def _column(kappa, width: int):
    """κ [n, 1, dk], a key channel a lane -> [n, dk, width], a key channel
    a sublane (the state's rows), the same on every lane."""
    n, _, dk = kappa.shape
    col = jnp.swapaxes(jnp.broadcast_to(kappa, (n, SUBLANE, dk)), 1, 2)
    return jnp.broadcast_to(col[:, :, :1], (n, dk, width))


def _fwd_kernel(q_ref, k_ref, kb_ref, vb_ref, g_ref, m_ref, o_ref, *rest,
                Q: int, Z: int, keep: bool):
    """A step of the forward: :func:`_blocks` for all its chunks at once,
    then the states' chain, ONE product a chunk: ``[G; q̃] · S₀``, ``S₁ =
    κ ⊙ S₀ + C − G S₀``, ``o = q̃ S₀ + P U``."""
    s_ref = rest[0] if keep else None
    state, gq_ref, c_ref, pu_ref, kap_ref = rest[-5:]
    nc = m_ref.shape[1]
    dk, dv = q_ref.shape[2], vb_ref.shape[2]
    cd = q_ref.dtype
    exact = cd == jnp.float32
    z = pl.program_id(2)

    @pl.when(z == 0)
    def _():
        state[...] = jnp.zeros(state.shape, state.dtype)

    ops = _real([_chunks(r, nc) for r in (q_ref, k_ref, kb_ref, vb_ref,
                                          g_ref)], z, Z)
    G, C, qt, PU, kappa = _blocks(*ops, m_ref[0], exact)
    gq_ref[:, :dk, :] = G.astype(cd)
    gq_ref[:, dk:, :] = qt.astype(cd)
    c_ref[...] = C
    pu_ref[...] = PU
    kap_ref[...] = _column(kappa, dv)

    def chunk(ci, carry):
        rows = pl.ds(pl.multiple_of(ci * Q, Q), Q)
        S0 = state[...]  # [dk, dv]
        S0c = S0.astype(cd)
        if keep:
            s_ref[0, ci, 0] = S0c
        out = _dot(gq_ref[ci], S0c, None, exact)  # [G; q̃] · S₀
        state[...] = kap_ref[ci] * S0 + c_ref[ci] - out[:dk]
        o_ref[0, rows, :] = out[dk:] + pu_ref[ci]
        return carry

    _walk(nc, chunk)


def _bwd_kernel(q_ref, k_ref, kb_ref, vb_ref, g_ref, m_ref, s_ref, do_ref,
                dq_ref, dk_ref, dkb_ref, dvb_ref, dg_ref,
                dstate, gt_ref, qd_ref, kap_ref, ds_ref, *, Z: int):
    """A step of the backward, in the forward's phases, the chunks
    reversed: :func:`_blocks` again for all the step's chunks at once,
    under ``jax.vjp``; the chain ``dS₀ = κ ⊙ dS₁ − Gᵀ dS₁ + q̃ᵀ do`` from
    the step's last chunk to its first, ONE product a chunk, each chunk's
    ``dS₁`` left in a scratch; last the cotangents of ``G``, ``C``, ``q̃``,
    ``P U`` and ``κ`` from those ``dS₁`` and the kept states, pulled back
    to every operand for all the chunks at once."""
    nc = m_ref.shape[1]
    dk, dv = q_ref.shape[2], vb_ref.shape[2]
    cd, f32 = q_ref.dtype, jnp.float32
    exact = cd == f32
    zr = pl.program_id(2)
    step = pl.num_programs(2) - 1 - zr

    @pl.when(zr == 0)
    def _():
        dstate[...] = jnp.zeros(dstate.shape, f32)

    q, k, kb, vb, g, do, S0c = _real(
        [_chunks(r, nc) for r in (q_ref, k_ref, kb_ref, vb_ref, g_ref,
                                  do_ref)] + [s_ref[0, :, 0]], step, Z)
    tiles = m_ref[0]
    (G, _, qt, _, kappa), pull = jax.vjp(
        lambda *a: _blocks(*a, tiles, exact), q, k, kb, vb, g)
    doc = do.astype(cd)
    gt_ref[...] = jnp.swapaxes(G, 1, 2).astype(cd)
    qd_ref[...] = _dots("nqk,nqw->nkw", qt.astype(cd), doc, exact)
    kap_ref[...] = _column(kappa, dv)

    def chunk(i, carry):  # the step's chunks from its last to its first
        ci = nc - 1 - i
        dS1 = dstate[...]
        ds_ref[ci] = dS1
        dstate[...] = (kap_ref[ci] * dS1 + qd_ref[ci]
                       - _dot(gt_ref[ci], dS1.astype(cd), None, exact))
        return carry

    _walk(nc, chunk)
    dS1 = ds_ref[...]  # [n, dk, dv]: d of the state LEAVING each chunk
    both = _dots("naw,nkw->nak", jnp.concatenate([dS1.astype(cd), doc],
                                                 axis=1), S0c, exact)
    held = both[:, :dk]  # dS₁ S₀ᵀ: −dG, and dκ on its diagonal
    d_kappa = jnp.sum(jnp.where(_own(held.shape), held, 0.0), axis=1,
                      keepdims=True)
    grads = pull((-held, dS1, both[:, dk:], do.astype(f32), d_kappa))
    for ref, d in zip((dq_ref, dk_ref, dkb_ref, dvb_ref, dg_ref), grads):
        ref[0] = d.reshape(ref.shape[1:])


def _specs(H: int, dk: int, dv: int, Q: int, Z: int, nc: int, reverse: bool):
    steps = pl.cdiv(Z, nc)

    def at(z):
        return steps - 1 - z if reverse else z

    key = pl.BlockSpec((1, nc * Q, dk), lambda b, h, z: (b, at(z), h))
    val = pl.BlockSpec((1, nc * Q, dv), lambda b, h, z: (b, at(z), h))
    mask = pl.BlockSpec((1, nc, SUBLANE, LANE),
                        lambda b, h, z: (b, at(z), 0, 0))
    st = pl.BlockSpec((1, nc, 1, dk, dv), lambda b, h, z: (b, at(z), h, 0, 0))
    return key, val, mask, st, steps


def _own_precision(fn):
    """The kernels' bodies are traced where they are called: a context
    that asks every product for "highest" (a reference comparison around
    the program) must not reach the compute-dtype products in them —
    Mosaic takes no float32 precision on bfloat16 operands —; the float32
    ones ask for it themselves."""
    @functools.wraps(fn)
    def wrapped(*a, **kw):
        with jax.default_matmul_precision("default"):
            return fn(*a, **kw)

    return wrapped


def steps_of(chunks: int):
    """(forward, backward) chunks a grid step for a row of ``chunks``: the
    module's constants, or the largest power of two a shorter row holds.
    They need not divide the row: the last step is then short."""
    def most(n):
        while n > chunks:
            n //= 2
        return n

    return most(CHUNKS_PER_STEP), most(BWD_CHUNKS_PER_STEP)


def step_counts() -> Dict[Tuple[int, int, int, int, int], int]:
    """{(rows, length, heads, forward chunks a step, backward chunks a
    step): kernel calls traced} (either kernel's call counts one)."""
    return dict(_STEPS)


@_own_precision
def rule_fwd(q, k, kb, vb, g, seg, chunk: int, keep: bool = False,
             interpret: bool = False):
    """q, k, kb [R, T, H, dk] and vb [R, T, H, dv] in the compute dtype; g
    [R, T, H, dk] float32; seg [R, T] int; T a whole number of chunks.
    Returns (o [R, T, H, dv] float32, the state entering each chunk [R,
    chunks, H, dk, dv] in the compute dtype or, without ``keep``, None)."""
    R, T, H, dk = q.shape
    dv = vb.shape[3]
    Z = T // chunk
    nc = steps_of(Z)[0]
    _STEPS[(R, T, H) + steps_of(Z)] += 1
    key, val, mask, st, steps = _specs(H, dk, dv, chunk, Z, nc, False)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, Q=chunk, Z=Z, keep=keep),
        grid=(R, H, steps),
        in_specs=[key, key, key, val, key, mask],
        out_specs=[val] + ([st] if keep else []),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32),
                        pltpu.VMEM((nc, dk + chunk, dk), q.dtype),
                        pltpu.VMEM((nc, dk, dv), jnp.float32),
                        pltpu.VMEM((nc, chunk, dv), jnp.float32),
                        pltpu.VMEM((nc, dk, dv), jnp.float32)],
        out_shape=[jax.ShapeDtypeStruct((R, T, H * dv), jnp.float32)] + (
            [jax.ShapeDtypeStruct((R, Z, H, dk, dv), q.dtype)]
            if keep else []),
        name=FWD_NAME, **_params(interpret),
    )(q.reshape(R, T, H * dk), k.reshape(R, T, H * dk),
      kb.reshape(R, T, H * dk), vb.reshape(R, T, H * dv),
      g.reshape(R, T, H * dk), mask_tiles(seg, chunk, steps * nc))
    return out[0].reshape(R, T, H, dv), (out[1] if keep else None)


@_own_precision
def rule_bwd(q, k, kb, vb, g, seg, states, do, chunk: int,
             interpret: bool = False):
    """(dq, dk, dkb, dvb in the compute dtype, dg [R, T, H, dk] float32)
    from the forward's operands, its kept states and do."""
    R, T, H, dk = q.shape
    dv = vb.shape[3]
    Z = T // chunk
    nc = steps_of(Z)[1]
    _STEPS[(R, T, H) + steps_of(Z)] += 1
    key, val, mask, st, steps = _specs(H, dk, dv, chunk, Z, nc, True)
    cd, f32 = q.dtype, jnp.float32
    flat_k, flat_v = (R, T, H * dk), (R, T, H * dv)
    dq, dkey, dkb, dvb, dg = pl.pallas_call(
        functools.partial(_bwd_kernel, Z=Z),
        grid=(R, H, steps),
        in_specs=[key, key, key, val, key, mask, st, val],
        out_specs=[key, key, key, val, key],
        scratch_shapes=[pltpu.VMEM((dk, dv), f32),
                        pltpu.VMEM((nc, dk, dk), cd),
                        pltpu.VMEM((nc, dk, dv), f32),
                        pltpu.VMEM((nc, dk, dv), f32),
                        pltpu.VMEM((nc, dk, dv), f32)],
        out_shape=[jax.ShapeDtypeStruct(flat_k, cd)] * 3 + [
            jax.ShapeDtypeStruct(flat_v, cd),
            jax.ShapeDtypeStruct(flat_k, f32)],
        name=BWD_NAME, **_params(interpret),
    )(q.reshape(flat_k), k.reshape(flat_k), kb.reshape(flat_k),
      vb.reshape(flat_v), g.reshape(flat_k),
      mask_tiles(seg, chunk, steps * nc), states, do.reshape(flat_v))
    return (dq.reshape(q.shape), dkey.reshape(k.shape), dkb.reshape(kb.shape),
            dvb.reshape(vb.shape), dg.reshape(g.shape))
