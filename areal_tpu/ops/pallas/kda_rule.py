"""TPU chunked delta rule with a decay a key CHANNEL (Kimi Delta Attention)
for packed segment batches.

    S ← Diag(e^{g_t}) S;  δ_t = β_t (v_t − Sᵀ k_t);  S ← S + k_t δ_tᵀ;  o_t = Sᵀ q_t

(S [dk, dv] float32 a head, zero before a document's first token; ``g_t``
[dk] <= 0) — the chunked algorithm of ``models/gdn.gated_delta_rule`` at a
decay a channel, one grid step a (row, head, run of chunks), the chunk axis
innermost and sequential. The chunk algebra never sees β: it takes ``kb =
β ⊙ k`` and ``vb = β ⊙ v`` beside ``k`` (the plain entry's caller makes
them, and β's gradient is XLA's through those two products; the mixer's
entry makes them inside, below). With ``c`` the cumulated ``g`` inside a
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

**The mixer's ends inside** (``ends``, a static flag of the same two
kernels: :func:`mixer_fwd` / :func:`mixer_bwd`, what
``models/kda.rule_with_ends`` runs — ``gated_delta_rule.py``'s ``norms``
at a decay a channel). A grid step holds ONE head's 128 lanes of
everything, so all the mixer does by head between its convolution and its
out-projection is elementwise work and lane reductions on blocks that are
in VMEM anyway, and no XLA op sees an array by head (nor a float32 g or o,
nor ``kb`` / ``vb``):

 - operands as the matmuls and the convolution leave them, flat, a head a
   lane block: x [R, T, H · 3 dk] — a head's [q | k | v] side by side (the
   mixer permutes the WEIGHTS' columns, so one block is the head's three
   and d x leaves as one array) —, the decay's pre-activation ``a`` and the
   output gate [R, T, H · dk], all in the compute dtype; β rides row 4 of a
   per-head copy of the mask tile (:func:`mask_tiles`); ``-exp(A_log)``,
   ``dt_bias`` and the gated norm's weight ride one [8, 128] tile a head
   (:func:`mixer_parameters`);
 - on the way in (:func:`_ends_in`, for all the step's chunks at once): the
   SiLU, the two l2 norms in float32 (q times ``dk ** -0.5``), ``g = rate ⊙
   softplus(a + dt_bias)`` in float32, then ``kb``, ``vb`` — each rounded
   where ``models/kda.kda_mixer``'s XLA text rounds;
 - on the way out the forward keeps the step's o in VMEM and, after the
   states' chain, writes ``y = (rms(o) · w).astype(cd) ⊙ sigmoid(gate)`` in
   the compute dtype (:func:`_gated_norm`);
 - the backward takes d y, REBUILDS the chunks' o from the kept states and
   the blocks it rebuilds anyway (``q̃ S₀ + P U``: one more product a step,
   nothing kept for it), pulls d y back through :func:`_gated_norm` and the
   rule's cotangents through β's products and :func:`_ends_in` — both
   under ``jax.vjp`` in the kernel body, as :func:`_blocks` is, so the two
   passes cannot disagree on a rounding point — and writes d x, d a, d gate
   in the compute dtype, d β a token a lane into a tile (a lane sum on the
   MXU: ``gated_delta_rule._lane_sums``) and the parameter tile's
   gradient, summed over a row's steps where it stays.

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
    _l2_parts,
    _lane_sums,
    _params,
    _rms_parts,
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
_SEG, _ENTERS, _TO_END, _KEEPS, _BETA = 0, 1, 2, 3, 4
# The rows of the mixer's parameter tile (:func:`mixer_fwd`), a head's 128
# lanes of each: ``-exp(A_log)``, ``dt_bias``, the gated norm's weight.
_RATE, _DT_BIAS, _NORM = 0, 1, 2
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


@jax.custom_vjp
def _softplus(x):
    return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


_softplus.defvjp(lambda x: (_softplus(x), x),
                 lambda x, ct: (ct * jax.nn.sigmoid(x),))


def _ends_in(qr, kr, vr, a, rate, dt_bias, l2_eps: float, cd):
    """The mixer's way INTO the rule, for all the step's chunks at once:
    qr, kr, vr [n, Q, dk] as the convolution leaves them and ``a`` [n, Q,
    dk] as the decay's matmul leaves it, in the compute dtype; ``rate``
    (``-exp(A_log)``) and ``dt_bias`` [1, dk] float32 -> q, k, v in the
    compute dtype and g float32: the SiLU, the two l2 norms (float32, a
    lane reduction a token; q times ``dk ** -0.5``) and the decay's
    activation, each rounded where ``models/kda.kda_mixer``'s XLA text
    rounds. Differentiable in everything (the backward kernel's
    ``jax.vjp``); a token of zeros gives q = k = v = 0."""
    f32 = jnp.float32

    def silu(x):
        x = x.astype(f32)
        return (x * jax.nn.sigmoid(x)).astype(cd)

    q = (_l2_parts(silu(qr), l2_eps)[0] * qr.shape[-1] ** -0.5).astype(cd)
    k = _l2_parts(silu(kr), l2_eps)[0].astype(cd)
    return q, k, silu(vr), rate * _softplus(a.astype(f32) + dt_bias)


def _gated_norm(o, gate, w, eps: float, cd):
    """... and OUT of it: o [n, Q, dv] float32, ``gate`` [n, Q, dv] as the
    gate's matmul leaves it, ``w`` [1, dv] float32 -> the mixer's ``y =
    (rms(o) · w).astype(cd) ⊙ sigmoid(gate)`` in the compute dtype."""
    f32 = jnp.float32
    y = (_rms_parts(o, eps, o.shape[-1])[0] * w).astype(cd)
    return (y.astype(f32) * jax.nn.sigmoid(gate.astype(f32))).astype(cd)


def mask_tiles(seg, chunk: int, chunks: int = 0, beta=None):
    """seg [R, T] (T whole chunks) -> [R, chunks, 8, 128] float32, a
    chunk's tile: row 0 the segment ids on the first ``chunk`` lanes; 1
    the tokens (1.0) still in the document the row was in before the chunk
    (none in the row's first chunk); 2 those of the document the chunk
    ends in; 3 on every lane whether those two documents are one. With
    ``chunks`` past the row's own (whole grid steps), chunks of segment 0
    behind it. With ``beta`` [R, T, H] a tile a HEAD, [R, H, chunks, 8,
    128], whose row 4 holds the head's β, a token a lane (what
    ``gated_delta_rule.gate_tiles`` does for its gates: the transpose the
    kernels take of a tile anyway hands them β down the sublanes)."""
    R, T = seg.shape
    Q = chunk
    Z = max(chunks, T // Q)
    f32 = jnp.float32
    segz = jnp.pad(seg.astype(jnp.int32), ((0, 0), (0, Z * Q - T))
                   ).reshape(R, Z, Q)
    last = segz[:, :, -1]
    prev = jnp.pad(last, ((0, 0), (1, 0)), constant_values=-1)[:, :Z]

    def lanes(a):  # [.., Z, Q] -> [.., Z, 1, 128]
        return jnp.pad(a.astype(f32), ((0, 0),) * (a.ndim - 1)
                       + ((0, LANE - Q),))[..., None, :]

    keeps = jnp.broadcast_to((last == prev).astype(f32)[:, :, None, None],
                             (R, Z, 1, LANE))
    masks = jnp.concatenate(
        [lanes(segz), lanes(segz == prev[..., None]),
         lanes(segz == last[..., None]), keeps], axis=2)
    if beta is None:
        return jnp.concatenate(
            [masks, jnp.zeros((R, Z, SUBLANE - 4, LANE), f32)], axis=2)
    H = beta.shape[2]
    b = jnp.pad(beta.astype(f32), ((0, 0), (0, Z * Q - T), (0, 0)))
    b = jnp.moveaxis(b.reshape(R, Z, Q, H), 3, 1)  # [R, H, Z, Q]
    return jnp.concatenate(
        [jnp.broadcast_to(masks[:, None], (R, H) + masks.shape[1:]),
         lanes(b), jnp.zeros((R, H, Z, SUBLANE - 5, LANE), f32)], axis=3)


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


def _beta(tiles, Q: int):
    """A head's β of the step's chunks down the sublanes [n, Q, 1], from
    its tiles [n, 8, 128] (:func:`mask_tiles` with ``beta``)."""
    return jnp.swapaxes(tiles, 1, 2)[:, :Q, _BETA:_BETA + 1]


def _by_beta(k, v, b, cd):
    """``kb = β ⊙ k``, ``vb = β ⊙ v``, rounded where
    ``models/kda.channel_decay_rule`` rounds them."""
    f32 = jnp.float32
    return (k.astype(f32) * b).astype(cd), (v.astype(f32) * b).astype(cd)


def _fwd_kernel(*refs, Q: int, Z: int, keep: bool, ends):
    """A step of the forward: :func:`_blocks` for all its chunks at once,
    then the states' chain, ONE product a chunk: ``[G; q̃] · S₀``, ``S₁ =
    κ ⊙ S₀ + C − G S₀``, ``o = q̃ S₀ + P U``. With ``ends`` (the l2 norms'
    epsilon and the gated norm's: the mixer's entry, :func:`mixer_fwd`) the
    operands are the mixer's — a head's [q | k | v] as the convolution
    leaves them, the decay's and the output gate's pre-activations, the
    head's tiles with β and its parameter tile — :func:`_ends_in` makes the
    rule's from them, the step's o stay in VMEM (``pu_ref``) and what
    leaves, after the states' chain, is the mixer's ``y``
    (:func:`_gated_norm`)."""
    n_in = 6 if ends is None else 5
    m_ref, o_ref = refs[5 if ends is None else 3], refs[n_in]
    s_ref = refs[n_in + 1] if keep else None
    state, gq_ref, c_ref, pu_ref, kap_ref = refs[-5:]
    nc = m_ref.shape[-3]
    dk = dv = state.shape[0]
    cd = gq_ref.dtype
    exact = cd == jnp.float32
    z = pl.program_id(2)

    @pl.when(z == 0)
    def _():
        state[...] = jnp.zeros(state.shape, state.dtype)

    if ends is None:
        tiles = m_ref[0]
        ops = _real([_chunks(r, nc) for r in refs[:5]], z, Z)
    else:
        tiles, par = m_ref[0, 0], refs[4][...]
        x, a, gate = _real([_chunks(r, nc) for r in refs[:3]], z, Z)
        q, k, v, g = _ends_in(
            x[..., :dk], x[..., dk:2 * dk], x[..., 2 * dk:], a,
            par[_RATE:_RATE + 1], par[_DT_BIAS:_DT_BIAS + 1], ends[0], cd)
        ops = (q, k) + _by_beta(k, v, _beta(tiles, Q), cd) + (g,)
    G, C, qt, PU, kappa = _blocks(*ops, tiles, exact)
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
        if ends is None:
            o_ref[0, rows, :] = out[dk:] + pu_ref[ci]
        else:
            pu_ref[ci] = out[dk:] + pu_ref[ci]
        return carry

    _walk(nc, chunk)
    if ends is not None:
        y = _gated_norm(pu_ref[...], gate, par[_NORM:_NORM + 1], ends[1],
                        cd)
        o_ref[0] = y.reshape(o_ref.shape[1:])


def _bwd_kernel(*refs, Z: int, ends):
    """A step of the backward, in the forward's phases, the chunks
    reversed: :func:`_blocks` again for all the step's chunks at once,
    under ``jax.vjp``; the chain ``dS₀ = κ ⊙ dS₁ − Gᵀ dS₁ + q̃ᵀ do`` from
    the step's last chunk to its first, ONE product a chunk, each chunk's
    ``dS₁`` left in a scratch; last the cotangents of ``G``, ``C``, ``q̃``,
    ``P U`` and ``κ`` from those ``dS₁`` and the kept states, pulled back
    to every operand for all the chunks at once. With ``ends`` (as the
    forward's) the operands are the mixer's and ``do_ref`` holds the
    cotangent of its ``y``: the rule's operands come from
    :func:`_ends_in` under a ``jax.vjp`` of its own, the chunks' o are
    REBUILT from the kept states and the blocks (``q̃ S₀ + P U``), ``do``
    is formed through :func:`_gated_norm`'s vjp, and the rule's
    cotangents leave through β's two products (β's own a lane sum on the
    MXU, a token a lane, into a tile) and the first vjp: d [q | k | v], d
    a, d gate in the compute dtype, and the parameter tile's gradient
    summed over the row's steps where it stays."""
    n_in = 8 if ends is None else 7
    m_ref = refs[5 if ends is None else 3]
    s_ref, do_ref = refs[n_in - 2:n_in]
    outs = refs[n_in:n_in + 5]
    dstate, gt_ref, qd_ref, kap_ref, ds_ref = refs[-5:]
    nc = m_ref.shape[-3]
    dk = dv = dstate.shape[0]
    cd, f32 = gt_ref.dtype, jnp.float32
    exact = cd == f32
    zr = pl.program_id(2)
    step = pl.num_programs(2) - 1 - zr

    @pl.when(zr == 0)
    def _():
        dstate[...] = jnp.zeros(dstate.shape, f32)

    if ends is None:
        tiles = m_ref[0]
        q, k, kb, vb, g, do, S0c = _real(
            [_chunks(r, nc) for r in refs[:5] + (do_ref,)]
            + [s_ref[0, :, 0]], step, Z)
    else:
        tiles, par = m_ref[0, 0], refs[4][...]
        Q = do_ref.shape[1] // nc
        x, a, gate, dy, S0c = _real(
            [_chunks(r, nc) for r in refs[:3] + (do_ref,)]
            + [s_ref[0, :, 0]], step, Z)
        (q, k, v, g), pull_in = jax.vjp(
            lambda *xs: _ends_in(*xs, ends[0], cd),
            x[..., :dk], x[..., dk:2 * dk], x[..., 2 * dk:], a,
            par[_RATE:_RATE + 1], par[_DT_BIAS:_DT_BIAS + 1])
        b = _beta(tiles, Q)
        kb, vb = _by_beta(k, v, b, cd)
    (G, _, qt, PU, kappa), pull = jax.vjp(
        lambda *xs: _blocks(*xs, tiles, exact), q, k, kb, vb, g)
    qtc = qt.astype(cd)
    if ends is not None:  # dy through the gate and the norm to do
        o = _dots("nqk,nkw->nqw", qtc, S0c, exact) + PU
        _, pull_out = jax.vjp(
            lambda *xs: _gated_norm(*xs, ends[1], cd), o, gate,
            par[_NORM:_NORM + 1])
        do, dgate, dw = pull_out(dy)
    doc = do.astype(cd)
    gt_ref[...] = jnp.swapaxes(G, 1, 2).astype(cd)
    qd_ref[...] = _dots("nqk,nqw->nkw", qtc, doc, exact)
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
    if ends is None:
        for ref, d in zip(outs, grads):
            ref[0] = d.reshape(ref.shape[1:])
        return
    dx_ref, da_ref, dgate_ref, dtile_ref, dpar_ref = outs

    @pl.when(zr == 0)
    def _():
        dpar_ref[...] = jnp.zeros(dpar_ref.shape, f32)

    dq, dkey, dkb, dvb, dg = grads
    dkb, dvb = dkb.astype(f32), dvb.astype(f32)
    dqr, dkr, dvr, da, d_rate, d_bias = pull_in(
        (dq, (dkey.astype(f32) + b * dkb).astype(cd), (b * dvb).astype(cd),
         dg))
    for i, d in enumerate((dqr, dkr, dvr)):
        dx_ref[0, :, i * dk:(i + 1) * dk] = d.reshape(nc * Q, dk)
    da_ref[0] = da.reshape(da_ref.shape[1:])
    dgate_ref[0] = dgate.reshape(dgate_ref.shape[1:])
    # d β = Σ_d (dkb ⊙ k + dvb ⊙ v), a token a lane (row 0 of the tile)
    d_beta = _lane_sums(dkb * k.astype(f32) + dvb * v.astype(f32),
                        [(0, dk)], exact)[:, :SUBLANE]
    dtile_ref[0, 0] = jnp.concatenate(
        [d_beta, jnp.zeros((nc, SUBLANE, LANE - Q), f32)], axis=2)
    dpar_ref[0] += jnp.concatenate(
        [d_rate, d_bias, dw, jnp.zeros((SUBLANE - 3, dk), f32)], axis=0)


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


def _mixer_specs(dk: int, Q: int, Z: int, nc: int, reverse: bool):
    """The mixer's entry: a head's [q | k | v] (3 dk lanes of [R, T, H · 3
    dk]), a head's dk lanes of a [R, T, H · dk] array, its tiles of [R, H,
    chunks, 8, 128], its parameter tile of [8, H · dk], that tile's
    gradient a row [R, 8, H · dk], the kept states; the grid's steps."""
    key, _, _, st, steps = _specs(0, dk, dk, Q, Z, nc, reverse)

    def at(z):  # as :func:`_specs`
        return steps - 1 - z if reverse else z

    x = pl.BlockSpec((1, nc * Q, 3 * dk), lambda b, h, z: (b, at(z), h))
    tile = pl.BlockSpec((1, 1, nc, SUBLANE, LANE),
                        lambda b, h, z: (b, h, at(z), 0, 0))
    par = pl.BlockSpec((SUBLANE, dk), lambda b, h, z: (0, h))
    dpar = pl.BlockSpec((1, SUBLANE, dk), lambda b, h, z: (b, 0, h))
    return x, key, tile, par, dpar, st, steps


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
        functools.partial(_fwd_kernel, Q=chunk, Z=Z, keep=keep, ends=None),
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
        functools.partial(_bwd_kernel, Z=Z, ends=None),
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


def _mixer_dims(x, beta, chunk: int):
    R, T, W3 = x.shape
    H = beta.shape[2]
    return R, T, H, W3 // (3 * H), T // chunk


def mixer_parameters(A_log, dt_bias, norm):
    """A_log [H], dt_bias [H · dk], the gated norm's weight [dk] -> the
    parameter tile of :func:`mixer_fwd`, [8, H · dk] float32: row 0
    ``-exp(A_log)`` on each of a head's lanes, 1 ``dt_bias``, 2 the norm's
    weight under every head (jax differentiates this; the kernels return
    the tile's gradient)."""
    f32 = jnp.float32
    H, W = A_log.shape[0], dt_bias.shape[0]
    rows = jnp.stack([jnp.repeat(-jnp.exp(A_log.astype(f32)), W // H),
                      dt_bias.astype(f32), jnp.tile(norm.astype(f32), H)])
    return jnp.pad(rows, ((0, SUBLANE - 3), (0, 0)))


@_own_precision
def mixer_fwd(x, a, gate, beta, par, seg, chunk: int, eps,
              keep: bool = False, interpret: bool = False):
    """The mixer between its convolution and its out-projection, the SAME
    forward kernel with the ends inside (``ends``): x [R, T, H · 3 dk], a
    head's [q | k | v] side by side as the convolution leaves them, a and
    gate [R, T, H · dk] as the decay's and the output gate's matmuls leave
    them, all in the compute dtype; beta [R, T, H] float32; ``par``
    (:func:`mixer_parameters`); seg [R, T] int; T a whole number of chunks;
    ``eps`` (the l2 norms' epsilon, the gated norm's). Returns (y [R, T, H · dk] in the compute dtype, the state entering
    each chunk or, without ``keep``, None)."""
    R, T, H, dk, Z = _mixer_dims(x, beta, chunk)
    nc = steps_of(Z)[0]
    _STEPS[(R, T, H) + steps_of(Z)] += 1
    xs, key, tile, ps, _, st, steps = _mixer_specs(dk, chunk, Z, nc, False)
    cd, f32 = x.dtype, jnp.float32
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, Q=chunk, Z=Z, keep=keep,
                          ends=tuple(map(float, eps))),
        grid=(R, H, steps),
        in_specs=[xs, key, key, tile, ps],
        out_specs=[key] + ([st] if keep else []),
        scratch_shapes=[pltpu.VMEM((dk, dk), f32),
                        pltpu.VMEM((nc, dk + chunk, dk), cd),
                        pltpu.VMEM((nc, dk, dk), f32),
                        pltpu.VMEM((nc, chunk, dk), f32),
                        pltpu.VMEM((nc, dk, dk), f32)],
        out_shape=[jax.ShapeDtypeStruct((R, T, H * dk), cd)] + (
            [jax.ShapeDtypeStruct((R, Z, H, dk, dk), cd)] if keep else []),
        name=FWD_NAME, **_params(interpret),
    )(x, a, gate, mask_tiles(seg, chunk, steps * nc, beta), par)
    return out[0], (out[1] if keep else None)


@_own_precision
def mixer_bwd(x, a, gate, beta, par, seg, states, dy, chunk: int, eps,
              interpret: bool = False):
    """(dx, da, dgate in the compute dtype, dbeta [R, T, H] float32 and the
    parameter tile's gradient [8, H · dk] float32) from :func:`mixer_fwd`'s
    operands, its kept states and the cotangent of ``y``."""
    R, T, H, dk, Z = _mixer_dims(x, beta, chunk)
    nc = steps_of(Z)[1]
    _STEPS[(R, T, H) + steps_of(Z)] += 1
    xs, key, tile, ps, dps, st, steps = _mixer_specs(dk, chunk, Z, nc, True)
    cd, f32 = x.dtype, jnp.float32
    tiles = mask_tiles(seg, chunk, steps * nc, beta)
    dx, da, dgate, dtile, dpar = pl.pallas_call(
        functools.partial(_bwd_kernel, Z=Z, ends=tuple(map(float, eps))),
        grid=(R, H, steps),
        in_specs=[xs, key, key, tile, ps, st, key],
        out_specs=[xs, key, key, tile, dps],
        scratch_shapes=[pltpu.VMEM((dk, dk), f32),
                        pltpu.VMEM((nc, dk, dk), cd),
                        pltpu.VMEM((nc, dk, dk), f32),
                        pltpu.VMEM((nc, dk, dk), f32),
                        pltpu.VMEM((nc, dk, dk), f32)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, cd),
                   jax.ShapeDtypeStruct(a.shape, cd),
                   jax.ShapeDtypeStruct(gate.shape, cd),
                   jax.ShapeDtypeStruct(tiles.shape, f32),
                   jax.ShapeDtypeStruct((R,) + par.shape, f32)],
        name=BWD_NAME, **_params(interpret),
    )(x, a, gate, tiles, par, states, dy)
    # a token a lane of each chunk's tile, row 0 -> [R, T, H]
    dbeta = jnp.moveaxis(dtile[:, :, :, 0, :chunk], 1, 3).reshape(
        R, -1, H)[:, :T]
    return dx, da, dgate, dbeta, jnp.sum(dpar, axis=0)
