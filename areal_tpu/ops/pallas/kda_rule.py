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

**The decay does not leave the product** as a scalar factor does, and
``(k_i ⊙ e^{c_i})·(k_j ⊙ e^{−c_j})`` would take a POSITIVE exponent. Every
exponent here is that of a non-positive difference (``ssm._masked_exp``'s
contract), which takes a reference INSIDE the chunk, by sub-blocks of
``SUB`` tokens: a sub-block's rows against every EARLIER sub-block's
columns are one product of ``x_i ⊙ e^{c_i − c_ref}`` and ``k_j ⊙ e^{c_ref −
c_j}`` with ``c_ref`` the sub-block's first row (``j < ref <= i``: both
differences non-positive); inside a sub-block the sum over channels is
made element by element, a column of every sub-block at a time.

**One chunk's arithmetic is ONE function** (:func:`_chunk`), float32 gates,
``A``, ``M`` and state, matmul operands in the compute dtype and every sum
float32. The forward kernel walks a step's chunks with it; the backward
kernel walks them in reverse and takes ``jax.vjp`` OF THAT FUNCTION inside
the kernel body, from the state the forward kept entering each chunk and
the ``dS`` it carries — so the two passes cannot disagree on a mask, a
reference or a rounding point, and nothing [Q, Q] leaves VMEM. Only the
inverse has a cotangent written by hand (``−Mᵀ M̄ Mᵀ``). The state rides
TRANSPOSED ([dv, dk]: the decay of a key channel is then a lane's).

The kernels' device ops are named ``kda_rule_fwd`` / ``kda_rule_bwd`` under
the caller's scope (not jitted by themselves: the benchmark reads the rule
by its scope ``kda_rule``). CPU/testing: ``interpret=True``;
tests/test_tpu_compile.py compiles them for a described v5e.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from areal_tpu.ops.pallas.gated_delta_rule import (  # noqa: F401
    _NT,
    _TN,
    LANE,
    SUBLANE,
    _dot,
    _inverses,
    _params,
    chunks_per_step,
    fits_device,
)

FWD_NAME, BWD_NAME = "kda_rule_fwd", "kda_rule_bwd"
# Tokens a sub-block (the module's docstring): the chunk's 64 are four.
SUB = 16
# The rows of a chunk's mask tile (:func:`mask_tiles`).
_SEG, _ENTERS, _TO_END, _KEEPS = 0, 1, 2, 3


def supported(chunk: int, heads: int, dk: int, dv: int, dtype) -> bool:
    """What the kernels take: chunks of 64, heads of one lane tile, float32
    or bfloat16."""
    return (chunk == 64 and dk == LANE and dv == LANE and heads >= 1
            and jnp.dtype(dtype) in (jnp.dtype(jnp.float32),
                                     jnp.dtype(jnp.bfloat16)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _unit_inverse(A, exact: bool):
    """``(I + A)^-1`` of ONE strictly lower-triangular float32 [Q, Q], by
    the delta rule kernel's blocks (``gated_delta_rule._inverses`` takes
    pairs: the second of this one is zeros)."""
    Q = A.shape[0]
    pair = jnp.concatenate([A, jnp.zeros_like(A)], axis=1)[None]
    return _inverses(pair, exact)[0][:, :Q]


def _unit_inverse_fwd(A, exact):
    M = _unit_inverse(A, exact)
    return M, M


def _unit_inverse_bwd(exact, M, ct):
    cd = jnp.float32 if exact else jnp.bfloat16
    Mc = M.astype(cd)
    left = _dot(Mc, ct.astype(cd), _TN, exact)  # Mᵀ M̄
    return (-_dot(left.astype(cd), Mc, _NT, exact),)


_unit_inverse.defvjp(_unit_inverse_fwd, _unit_inverse_bwd)


def _chunk(q, k, kb, vb, g, St, tile, exact: bool):
    """One chunk of one head (the module's docstring): q, k, kb [Q, dk] and
    vb [Q, dv] in the compute dtype, g [Q, dk] float32, ``St`` the state
    entering the chunk TRANSPOSED [dv, dk] float32, ``tile`` the chunk's
    mask tile [8, 128] -> (o [Q, dv], the state leaving it [dv, dk]), both
    float32. Differentiable in everything but the tile, with no slice of a
    differentiated array (rows are taken by masked sums)."""
    Q, dk = q.shape
    cd, f32 = q.dtype, jnp.float32
    nb = Q // SUB
    cols = jnp.swapaxes(tile, 0, 1)  # the same quantities down the sublanes
    seg_r, seg_c = tile[_SEG:_SEG + 1, :Q], cols[:Q, _SEG:_SEG + 1]
    ent_c = cols[:Q, _ENTERS:_ENTERS + 1]
    end_c = cols[:Q, _TO_END:_TO_END + 1]
    keeps = tile[_KEEPS:_KEEPS + 1, :dk]
    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, dk), 0)
    same = seg_c == seg_r

    def row_of(x, r: int):  # [Q, dk] -> its row r as [1, dk]
        return jnp.sum(jnp.where(row == r, x, 0.0), axis=0, keepdims=True)

    def by_block(x):  # [Q, dk] -> [sub-blocks, SUB, dk]
        return x.reshape(nb, SUB, dk)

    def block_row(x, r: int):  # row r of every sub-block, on all its rows
        inner = jax.lax.broadcasted_iota(jnp.int32, (nb, SUB, dk), 1)
        one = jnp.sum(jnp.where(inner == r, by_block(x), 0.0), axis=1,
                      keepdims=True)
        return jnp.broadcast_to(one, (nb, SUB, dk)).reshape(Q, dk)

    c = _dot((ii >= jj).astype(f32), g, None, True)  # the inclusive cumsum
    qf, kf, kbf = (a.astype(f32) for a in (q, k, kb))
    # ---- a sub-block's rows against the earlier sub-blocks' columns
    up = jnp.exp(c - block_row(c, 0))  # e^{c_i − c_ref}, ref <= i
    kb_up, q_up = (kbf * up).astype(cd), (qf * up).astype(cd)
    A, P = jnp.zeros((Q, Q), f32), jnp.zeros((Q, Q), f32)
    for I in range(1, nb):
        ref = row_of(c, I * SUB)
        down = jnp.where(row < I * SUB,
                         kf * jnp.exp(jnp.minimum(ref - c, 0.0)), 0.0
                         ).astype(cd)  # k_j e^{c_ref − c_j}, j < ref
        mine = (ii >= I * SUB) & (ii < (I + 1) * SUB)
        A = A + jnp.where(mine, _dot(kb_up, down, _NT, exact), 0.0)
        P = P + jnp.where(mine, _dot(q_up, down, _NT, exact), 0.0)
    # ---- inside a sub-block: column r of every sub-block at a time
    for r in range(SUB):
        E = jnp.exp(jnp.minimum(c - block_row(c, r), 0.0)) * block_row(kf, r)
        at = jj == (ii // SUB) * SUB + r
        A = jnp.where(at, jnp.sum(kbf * E, axis=1, keepdims=True), A)
        P = jnp.where(at, jnp.sum(qf * E, axis=1, keepdims=True), P)
    A = jnp.where(same & (ii > jj), A, 0.0)
    P = jnp.where(same & (ii >= jj), P, 0.0).astype(cd)
    M = _unit_inverse(A, exact).astype(cd)
    # ---- the states: what reads the entering one, what the chunk leaves
    c_end = row_of(c, Q - 1)
    e = jnp.where(ent_c > 0, jnp.exp(c), 0.0)
    t = jnp.where(end_c > 0, jnp.exp(c_end - c), 0.0)
    kappa = jnp.where(keeps > 0, jnp.exp(c_end), 0.0)
    S0c = St.astype(cd)
    R = vb.astype(f32) - _dot((kbf * e).astype(cd), S0c, _NT, exact)
    delta = _dot(M, R.astype(cd), None, exact).astype(cd)
    o = (_dot((qf * e).astype(cd), S0c, _NT, exact)
         + _dot(P, delta, None, exact))
    St1 = kappa * St + _dot(delta, (kf * t).astype(cd), _TN, exact)
    return o, St1


def mask_tiles(seg, chunk: int):
    """seg [R, T] (T whole chunks) -> [R, chunks, 8, 128] float32, a
    chunk's tile: row 0 the segment ids on the first ``chunk`` lanes; 1
    the tokens (1.0) still in the document the row was in before the chunk
    (none in the row's first chunk); 2 those of the document the chunk
    ends in; 3 on every lane whether those two documents are one."""
    R, T = seg.shape
    Q, Z = chunk, T // chunk
    f32 = jnp.float32
    segz = seg.astype(jnp.int32).reshape(R, Z, Q)
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


def _fwd_kernel(q_ref, k_ref, kb_ref, vb_ref, g_ref, m_ref, o_ref, *rest,
                Q: int, keep: bool):
    s_ref = rest[0] if keep else None
    state = rest[-1]
    nc = m_ref.shape[1]
    cd = q_ref.dtype
    exact = cd == jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros(state.shape, state.dtype)

    def chunk(ci, carry):
        rows = pl.ds(pl.multiple_of(ci * Q, Q), Q)
        St = state[...]
        if keep:
            s_ref[0, ci, 0] = St.astype(cd)
        o, St1 = _chunk(q_ref[0, rows, :], k_ref[0, rows, :],
                        kb_ref[0, rows, :], vb_ref[0, rows, :],
                        g_ref[0, rows, :], St, m_ref[0, ci], exact)
        o_ref[0, rows, :] = o
        state[...] = St1
        return carry

    jax.lax.fori_loop(0, nc, chunk, 0)


def _bwd_kernel(q_ref, k_ref, kb_ref, vb_ref, g_ref, m_ref, s_ref, do_ref,
                dq_ref, dk_ref, dkb_ref, dvb_ref, dg_ref, dstate, *, Q: int):
    nc = m_ref.shape[1]
    cd, f32 = q_ref.dtype, jnp.float32
    exact = cd == f32

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros(dstate.shape, f32)

    def chunk(i, carry):  # the step's chunks from its last to its first
        ci = nc - 1 - i
        rows = pl.ds(pl.multiple_of(ci * Q, Q), Q)
        tile = m_ref[0, ci]
        _, pull = jax.vjp(
            lambda q, k, kb, vb, g, St: _chunk(q, k, kb, vb, g, St, tile,
                                               exact),
            q_ref[0, rows, :], k_ref[0, rows, :], kb_ref[0, rows, :],
            vb_ref[0, rows, :], g_ref[0, rows, :],
            s_ref[0, ci, 0].astype(f32))
        dq, dk, dkb, dvb, dg, dS0 = pull(
            (do_ref[0, rows, :].astype(f32), dstate[...]))
        dq_ref[0, rows, :] = dq
        dk_ref[0, rows, :] = dk
        dkb_ref[0, rows, :] = dkb
        dvb_ref[0, rows, :] = dvb
        dg_ref[0, rows, :] = dg
        dstate[...] = dS0
        return carry

    jax.lax.fori_loop(0, nc, chunk, 0)


def _specs(R: int, T: int, H: int, dk: int, dv: int, Q: int, nc: int,
           reverse: bool):
    steps = T // Q // nc

    def at(z):
        return steps - 1 - z if reverse else z

    key = pl.BlockSpec((1, nc * Q, dk), lambda b, h, z: (b, at(z), h))
    val = pl.BlockSpec((1, nc * Q, dv), lambda b, h, z: (b, at(z), h))
    mask = pl.BlockSpec((1, nc, SUBLANE, LANE),
                        lambda b, h, z: (b, at(z), 0, 0))
    st = pl.BlockSpec((1, nc, 1, dv, dk), lambda b, h, z: (b, at(z), h, 0, 0))
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


@_own_precision
def rule_fwd(q, k, kb, vb, g, seg, chunk: int, keep: bool = False,
             interpret: bool = False):
    """q, k, kb [R, T, H, dk] and vb [R, T, H, dv] in the compute dtype; g
    [R, T, H, dk] float32; seg [R, T] int; T a whole number of chunks.
    Returns (o [R, T, H, dv] float32, the state entering each chunk,
    transposed, [R, chunks, H, dv, dk] in the compute dtype or, without
    ``keep``, None)."""
    R, T, H, dk = q.shape
    dv = vb.shape[3]
    Z = T // chunk
    nc = chunks_per_step(Z)
    key, val, mask, st, steps = _specs(R, T, H, dk, dv, chunk, nc, False)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, Q=chunk, keep=keep),
        grid=(R, H, steps),
        in_specs=[key, key, key, val, key, mask],
        out_specs=[val] + ([st] if keep else []),
        scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
        out_shape=[jax.ShapeDtypeStruct((R, T, H * dv), jnp.float32)] + (
            [jax.ShapeDtypeStruct((R, Z, H, dv, dk), q.dtype)]
            if keep else []),
        name=FWD_NAME, **_params(interpret),
    )(q.reshape(R, T, H * dk), k.reshape(R, T, H * dk),
      kb.reshape(R, T, H * dk), vb.reshape(R, T, H * dv),
      g.reshape(R, T, H * dk), mask_tiles(seg, chunk))
    return out[0].reshape(R, T, H, dv), (out[1] if keep else None)


@_own_precision
def rule_bwd(q, k, kb, vb, g, seg, states, do, chunk: int,
             interpret: bool = False):
    """(dq, dk, dkb, dvb in the compute dtype, dg [R, T, H, dk] float32)
    from the forward's operands, its kept states and do."""
    R, T, H, dk = q.shape
    dv = vb.shape[3]
    nc = chunks_per_step(T // chunk, backward=True)
    key, val, mask, st, steps = _specs(R, T, H, dk, dv, chunk, nc, True)
    cd = q.dtype
    flat_k, flat_v = (R, T, H * dk), (R, T, H * dv)
    dq, dkey, dkb, dvb, dg = pl.pallas_call(
        functools.partial(_bwd_kernel, Q=chunk),
        grid=(R, H, steps),
        in_specs=[key, key, key, val, key, mask, st, val],
        out_specs=[key, key, key, val, key],
        scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
        out_shape=[jax.ShapeDtypeStruct(flat_k, cd)] * 3 + [
            jax.ShapeDtypeStruct(flat_v, cd),
            jax.ShapeDtypeStruct(flat_k, jnp.float32)],
        name=BWD_NAME, **_params(interpret),
    )(q.reshape(flat_k), k.reshape(flat_k), kb.reshape(flat_k),
      vb.reshape(flat_v), g.reshape(flat_k), mask_tiles(seg, chunk), states,
      do.reshape(flat_v))
    return (dq.reshape(q.shape), dkey.reshape(k.shape), dkb.reshape(kb.shape),
            dvb.reshape(vb.shape), dg.reshape(g.shape))
