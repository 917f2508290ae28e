"""TPU chunked state-space scan (SSD, Mamba-2) for packed segment batches.

    S_t = exp(Δ_t A) · S_{t-1} + Δ_t x_t ⊗ B_t       one scalar decay a head
    y_t = S_t · C_t                                   S zero before a document

The chunked algorithm of ``models/ssm.ssd_scan`` at the same chunk, one
grid step a (row, block of heads, chunk), the chunk axis innermost and
sequential; inside a step, a loop over the block's lane tiles of heads.
With ``cs`` the cumulative log-decay inside a chunk (float32), per head
and chunk of Q tokens:

    y  = ((E ⊙ C·Bᵀ) · x) + (C · S_inᵀ) ⊙ from_start
    S_out = S_in · exp(cs_last) · [the chunk ends in the entering document]
            + (x ⊙ to_end)ᵀ · B

``E_ij = Δ_j exp(cs_i − cs_j)`` where j <= i are tokens of one document,
else exactly 0 (the product with a masked ``C·Bᵀ``; the exponent is
clamped, so nothing overflows above the diagonal). Δ_j rides the exponent
as ``log Δ_j`` — ``cs − log Δ`` is one more [T, heads] array — and
``to_end`` the same way, so Δ·x is never formed and costs the kernel
nothing (a token of a row's padding has Δ = 0: its ``cs − log Δ`` is set
so large that exp gives exactly 0). The [Q, Q] mask, ``C·Bᵀ``, ``E`` and
their product exist in VMEM only, and only in the [128, 128] pieces at or
under the diagonal; the state [heads · P, N] float32 is a VMEM scratch
that rides the chunk axis. Matmul operands are the compute dtype; every
accumulation, ``exp``, ``cs`` and the state float32.

A row need be no whole number of chunks: x, B, C and y keep their length,
the last chunk's block reaches past the row's end, and what it reads there
is replaced by 0 (what it writes there is dropped); only the small [T,
heads] arrays are padded.

 - forward: x, B, C, per head cs and cs − log Δ (tokens in the lanes) and
   the segment ids in, y float32 out and — for a backward pass — the
   state ENTERING each chunk ([rows, chunks, heads · P, N] float32);
 - backward: the same grid with the chunks reversed, the gradient of the
   state as its carry; everything [Q, Q] is built TRANSPOSED (key token in
   the sublanes), so that no head needs a transpose: dx = Mᵀ·dy, dMᵀ =
   x·dyᵀ, and the heads' sum of dMᵀ ⊙ Eᵀ gives dB and dC with two matmuls
   a piece and grid step. The decays' gradient needs no [Q, Q] sum: every
   term of y_i carries exp(cs_i) and every term x_j enters carries Δ_j
   exp(−cs_j), so d cs = Σ_p dy·y − Σ_p x·dx a token and head (and dΔ's
   own part is Σ_p x·dx / Δ), plus the carry's terms at a chunk's last
   token — sums over a head's own lanes, made on the MXU against a 0/1
   matrix from the float32 values split into bfloat16 parts.

Heads narrower than the 128 lanes share a lane tile (two heads of 64):
each head's [Q, Q] pieces multiply the tile's [Q, 128] operand — the MXU
is 128 wide anyway — and a lane select keeps the head's own columns.

The loop over a step's tiles is a ``fori_loop``: a tile's x / y are whole
lane tiles at a traced offset. What a tile needs of the [heads, Q] decays
it cannot slice out at a traced head, so the MXU picks it, exactly (the
float32 values as three bfloat16 parts against 0/1 matrices, float32
accumulation): its heads' ROWS (``_tile_rows``), and the COLUMNS — a
token's value down the sublanes on every lane of a block — by one
transposed-LHS matmul a tile against a constant (``column_picks``). A
column by a transpose and a lane permute a vreg instead made the loop's
body a chain on the XLU, 55 % of the forward's bundles (PERF.md §6, PR 50
(8)). Of the three columns a pass needs, one rides that matmul beside the
one the decay block needs; the third is an ``exp`` of the latter. With the
tiles unrolled instead — static slices — every program that holds a scan
paid 0.2-0.5 s more of set-up to trace and lower ~3,000 operations.

The kernels' device ops are named ``ssd_scan_fwd`` / ``ssd_scan_bwd``
under the caller's scope (``scan_fwd`` / ``scan_bwd`` are NOT jitted by
themselves: a jitted function's ops lose the name stack around its call,
and the benchmark reads the scan by its scope). CPU/testing:
``interpret=True``; tests/test_tpu_compile.py compiles them for a
described v5e.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
SUBLANE = 8
FWD_NAME, BWD_NAME = "ssd_scan_fwd", "ssd_scan_bwd"
# Heads a grid step (tools/ssd_scan_sweep.py; PERF.md §5, PR 50): the
# [Q, Q] mask and C·Bᵀ are made once a step, and the picks' matmuls grow
# with the heads of a step — forward + backward at Granite's row read
# 1.16 ms at 16 and 1.28 at 32.
HEADS_PER_STEP = 16
# What a kernel may take of VMEM: half of a v5e's 128 MiB (the default, 16
# MiB, is for a kernel beside XLA's own fusions). A chip that has not twice
# this runs the XLA form (:func:`fits_device`).
VMEM_LIMIT = 64 * 1024 * 1024

# No exponent of a decay the mask keeps passes log Δ; one the mask drops
# (above the diagonal: cs_i − cs_j > 0) is cut here, and its exp times the
# masked C·Bᵀ's 0 is 0.
CLAMP = 80.0
# cs − log Δ of a token of a row's padding (Δ = 0): finite, so that a 0/1
# matmul may carry it, and so large that exp(cs_i − it) is exactly 0.
NEVER = 1e30

_NT = (((1,), (1,)), ((), ()))  # a · bᵀ
_TN = (((0,), (0,)), ((), ()))  # aᵀ · b


def _tile(head_dim: int) -> Tuple[int, int]:
    """(heads a lane tile, the tile's lanes)."""
    if head_dim >= LANE:
        return 1, head_dim
    return LANE // head_dim, LANE


def supported(chunk: int, heads: int, head_dim: int, groups: int,
              state: int) -> bool:
    """Chunk and state in whole lanes, a group's heads in whole lane
    tiles (a head of 64 beside another of its group) and, where there is
    more than one group, in whole sublanes — what the kernel takes (a row
    of any length) —, and a step's :func:`column_picks`, twice buffered,
    in half of ``VMEM_LIMIT`` (they grow with the square of a step's
    heads: 120 heads that 16 does not divide are refused)."""
    if chunk % LANE or state % LANE or heads % groups:
        return False
    if head_dim % LANE and (LANE % head_dim or head_dim % SUBLANE):
        return False
    hb = heads_per_step(heads, head_dim, groups)
    hg, W = _tile(head_dim)
    picks = (hb // hg) * 6 * hb * (hg + 1) * W * 2  # bytes
    return hb % hg == 0 and hb <= LANE and 4 * picks <= VMEM_LIMIT and (
        hb % SUBLANE == 0 or hb == heads)


def fits_device() -> bool:
    """Whether the attached chip's VMEM is at least twice ``VMEM_LIMIT``
    (v5e, v6e: 128 MiB; v2 to v4: 16, v5p and v7x: 64 — not). Where no TPU
    is attached — a lowering for a described chip — there is none to ask:
    True."""
    try:
        return pltpu.get_tpu_info().vmem_capacity_bytes >= 2 * VMEM_LIMIT
    except ValueError:  # "Unsupported TPU device kind": no TPU
        return True


def heads_per_step(heads: int, head_dim: int, groups: int) -> int:
    """Heads a grid step holds: ``HEADS_PER_STEP`` where that divides a
    group's heads in whole lane tiles and sublanes, else all of a group's
    (one group: all heads — a block that spans its array needs no tile)."""
    per_group, hg = heads // groups, _tile(head_dim)[0]
    hb = HEADS_PER_STEP
    if per_group % hb == 0 and hb % hg == 0 and hb % SUBLANE == 0:
        return hb
    return per_group


def _dot(a, b, dims=None):
    if dims is None:
        return jnp.dot(a, b, preferred_element_type=jnp.float32)
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _own(vals, P: int):
    """A tile's heads' [Q, W] arrays -> [Q, W]: head k's on head k's own P
    lanes."""
    out = vals[0]
    for k in range(1, len(vals)):
        out = jnp.where(_own_lanes(k, P, *out.shape), vals[k], out)
    return out


def _spread_down(vals, P: int):
    """A tile's heads' scalars ([1, 1] each) -> [heads · P, 1]: each
    head's scalar down its own P sublanes of the tile's state."""
    sub = jax.lax.broadcasted_iota(jnp.int32, (len(vals) * P, 1), 0)
    out = jnp.broadcast_to(vals[0], sub.shape)
    for k in range(1, len(vals)):
        out = jnp.where(sub >= k * P, vals[k], out)
    return out


def _own_lanes(k: int, P: int, rows: int, W: int):
    """[rows, W] bool: the lanes of head k of a tile."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, W), 1)
    return (lane >= k * P) & (lane < (k + 1) * P)


def _rows(r: int):
    """The tokens of a chunk's 128-token block ``r``."""
    return slice(r * LANE, (r + 1) * LANE)


def _parts(v):
    """float32 -> three bfloat16 arrays that sum to it (8 bits each, all
    24) — what a 0/1 matmul moves without rounding."""
    out = []
    for _ in range(3):
        part = v.astype(jnp.bfloat16)
        v = v - part.astype(jnp.float32)
        out.append(part)
    return out


def column_picks(hb: int, P: int):
    """[tiles, 6 · hb, (hg + 1) · W] bfloat16, 0/1. Tile t's matrix takes
    the [6 · hb, Q] stack of two [hb, Q] float32 arrays' bfloat16 parts
    (part, array, head down the rows) to [Q, (hg + 1) · W], tokens down
    the sublanes — what a kernel cannot slice out at a traced head (a
    column at a traced lane) and what costs a lane permute a vreg where it
    can: the first array of each of the tile's hg heads across a W-lane
    block of its own, then the second over one more block, a head's value
    on the head's own P lanes. The MXU does it, exactly. Made by XLA (a
    constant), read once a kernel."""
    hg, W = _tile(P)
    r = jnp.arange(6 * hb)[None, :, None]
    j = jnp.arange((hg + 1) * W)[None, None, :]
    t = jnp.arange(hb // hg)[:, None, None]
    second = j >= hg * W
    head = t * hg + jnp.where(second, (j - hg * W) // P, j // W)
    return (((r % (2 * hb)) // hb == second) & (r % hb == head)).astype(
        jnp.bfloat16)


def _columns(first, second):
    """Two [Hb, Q] float32 arrays -> the [6 · Hb, Q] bfloat16 stack that
    :func:`column_picks` reads."""
    return jnp.concatenate(_parts(jnp.concatenate([first, second], axis=0)),
                           axis=0)


def _row_stack(a, scalars, W: int):
    """[Hb, Q] float32 and a head's ``scalars`` ([Hb, 1] each, riding W
    more lanes each, on every one of them) -> [Hb, 3 · (Q + W ·
    len(scalars))] bfloat16 for :func:`_tile_rows`: the three parts side
    by side."""
    Hb = a.shape[0]
    return jnp.concatenate(_parts(jnp.concatenate(
        [a] + [jnp.broadcast_to(v, (Hb, W)) for v in scalars], axis=1)),
        axis=1)


def _tile_rows(stack, t, hg: int):
    """Tile ``t``'s heads' rows of :func:`_row_stack` (``t`` traced: a row
    at a traced sublane loads only from whole tiles) — a 0/1 matmul picks
    them, exactly: head k in row k of [8.., Q + W · scalars]."""
    R8 = -(-hg // SUBLANE) * SUBLANE
    Hb, w = stack.shape[0], stack.shape[1] // 3
    r = jax.lax.broadcasted_iota(jnp.int32, (R8, Hb), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (R8, Hb), 1)
    out = _dot(((c == t * hg + r) & (r < hg)).astype(jnp.bfloat16), stack)
    return out[:, :w] + out[:, w:2 * w] + out[:, 2 * w:]


def _through(prev, last, cs):
    """[Hb, 1]: exp(cs_last) where the chunk ends in the document it
    entered in, else 0 — what the entering state keeps."""
    return jnp.where(prev == last, jnp.exp(cs[:, -1:]), 0.0)


def _lanes(col, W: int):
    """A [Q, 1] bool column of tokens -> [Q, W] float32, 1 / 0."""
    return jnp.broadcast_to(col.astype(jnp.float32), (col.shape[0], W))


def _seg_column(seg):
    """Segment ids [1, Q] -> [Q, 1] (as float32: ids are small)."""
    Q = seg.shape[1]
    return jnp.broadcast_to(seg.astype(jnp.float32), (SUBLANE, Q)).T[:, :1]


def _in_row(z, Q: int, T: int):
    """[Q, 1] bool: the tokens of chunk ``z`` that the row holds; None
    where every chunk is whole."""
    if T % Q == 0:
        return None
    return jax.lax.broadcasted_iota(jnp.int32, (Q, 1), 0) + z * Q < T


def _kept(valid, a):
    """``a`` [Q, .] with 0 past the row's end (a block that reaches past it
    reads what happens to lie there)."""
    return a if valid is None else jnp.where(valid, a, jnp.zeros_like(a))


def _fwd_tile(xt, S, Bv, Cv, cbm, cs_i, cd_j, entered, to_end, through,
              P: int):
    """One lane tile of heads. xt [Q, W]; S [W, N] float32, the entering
    state; ``cbm[r][c]`` the masked C·Bᵀ of query block r and key block c
    <= r, [128, 128]; a head: ``cs_i`` [Q, W] (a token's on every lane),
    ``cd_j`` a block [1, 128] (cs − log Δ), ``through`` [1, 1]; ``entered``
    [Q, W] 1 / 0, the tokens still in the entering document; ``to_end``
    [Q, W], a head's on its own lanes. Returns (y [Q, W] float32, the
    state the chunk leaves)."""
    Q, W = xt.shape
    cd = xt.dtype
    from_start = _own([jnp.exp(c) for c in cs_i], P) * entered
    ys = _dot(Cv, S.astype(cd), _NT) * from_start
    own = [_own_lanes(k, P, LANE, W) for k in range(len(cs_i))]
    out = []
    for r in range(Q // LANE):
        y = None
        for k in range(len(cs_i)):
            part = None
            for c in range(r + 1):
                e = jnp.exp(jnp.minimum(
                    cs_i[k][_rows(r), :LANE] - cd_j[k][c], CLAMP))
                m = _dot((e * cbm[r][c]).astype(cd), xt[_rows(c)])
                part = m if part is None else part + m
            y = part if y is None else jnp.where(own[k], part, y)
        out.append(y + ys[_rows(r)])
    xs = (xt.astype(jnp.float32) * to_end).astype(cd)
    return (jnp.concatenate(out, axis=0) if len(out) > 1 else out[0],
            S * _spread_down(through, P) + _dot(xs, Bv, _TN))


def _fwd_kernel(ends_ref, seg_ref, x_ref, b_ref, c_ref, cs_ref, cd_ref,
                pick_ref, y_ref, *rest, P: int, T: int, keep: bool):
    s_ref, state = rest if keep else (None, rest[0])
    b, z = pl.program_id(0), pl.program_id(2)
    Q, HW = x_ref.shape[1:]
    hg, W = _tile(P)
    nb = Q // LANE
    f32 = jnp.float32

    @pl.when(z == 0)
    def _():
        state[...] = jnp.zeros(state.shape, state.dtype)

    if keep:
        s_ref[0, 0] = state[...]
    valid = _in_row(z, Q, T)
    seg = seg_ref[0, 0]  # [1, Q]
    segc = _seg_column(seg)
    Bv, Cv = _kept(valid, b_ref[0]), _kept(valid, c_ref[0])  # [Q, N]
    # C·Bᵀ where query i (sublanes) may read key j (lanes), a [128, 128]
    # piece at a time: the pieces above the diagonal are never made
    under = (jax.lax.broadcasted_iota(jnp.int32, (LANE, LANE), 0)
             >= jax.lax.broadcasted_iota(jnp.int32, (LANE, LANE), 1))
    cbm = [[jnp.where(
        (segc[_rows(r)] == seg[:, _rows(c)].astype(f32))
        & (under if c == r else True),
        _dot(Cv[_rows(r)], Bv[_rows(c)], _NT), 0.0)
        for c in range(r + 1)] for r in range(nb)]
    cs, cd_ = cs_ref[0, 0], cd_ref[0, 0]  # [Hb, Q]
    prev, last = ends_ref[b, z], ends_ref[b, z + 1]
    # Δ exp(cs_last − cs) on the tokens of the document the chunk ends in
    to_end = jnp.where(seg == last, jnp.exp(cs[:, -1:] - cd_), 0.0)
    columns = _columns(cs, to_end)
    stack = _row_stack(cd_, [_through(prev, last, cs)], W)
    entered = _lanes(segc == prev.astype(f32), W)

    def cols(t):
        return pl.ds(pl.multiple_of(t * W, W), W)

    def tile(t, carry):
        xt, S = x_ref[0, :, cols(t)], state[cols(t), :]
        rows = _tile_rows(stack, t, hg)
        col = _dot(columns, pick_ref[t], _TN)  # [Q, (hg + 1) · W]
        y_ref[0, :, cols(t)], state[cols(t), :] = _fwd_tile(
            _kept(valid, xt), S, Bv, Cv, cbm,
            [col[:, k * W:(k + 1) * W] for k in range(hg)],
            [[rows[k:k + 1, _rows(c)] for c in range(nb)]
             for k in range(hg)],
            entered, col[:, hg * W:],
            [rows[k:k + 1, Q:Q + 1] for k in range(hg)], P)
        return carry

    jax.lax.fori_loop(0, HW // W, tile, 0)


def _head_sums(v, first, P: int, parts: int):
    """[Q, W] float32 -> [Q, 128] float32 whose lane ``first + k`` is the
    sum over head k's lanes of the tile (the other lanes 0): bfloat16
    matmuls against a 0/1 matrix, one for each of the ``parts`` bfloat16
    parts ``v`` is split into (8 bits of it each)."""
    W = v.shape[1]
    src = jax.lax.broadcasted_iota(jnp.int32, (W, LANE), 0)
    dst = jax.lax.broadcasted_iota(jnp.int32, (W, LANE), 1)
    pick = (src // P + first == dst).astype(jnp.bfloat16)
    out = None
    for _ in range(parts):
        part = v.astype(jnp.bfloat16)
        v = v - part.astype(jnp.float32)
        out = _dot(part, pick) if out is None else out + _dot(part, pick)
    return out


def _bwd_tile(first, xt, y, dy, S, dS, Bv, Cv, cbm_t, dcb_t, cs_i, cd_j,
              fs, ending, through, cs_last, P: int):
    """One lane tile of heads in the backward, transposed (key token j in
    the sublanes). ``first``: the tile's first head, a traced scalar (the
    lane its sums land in); xt, y, dy [Q, W]; S, dS [W, N] float32 (the
    entering state, the leaving state's gradient); a block: ``cbm_t`` the
    masked B·Cᵀ and ``dcb_t`` the heads' running sum of dMᵀ ⊙ Eᵀ, [r][i]
    of key block r and query block i >= r, [128, 128]; a head: ``cs_i`` a
    block [1, 128], ``cd_j`` [Q, W] (cs − log Δ, a token's on every lane),
    ``through`` [1, 1], ``cs_last`` [1, W] (on every lane); ``fs`` [Q, W],
    from_start, a head's on its own lanes; ``ending`` [Q, W] 1 / 0, the
    tokens of the document the chunk ends in. Returns (dx [Q, W] float32,
    the entering state's gradient, dB's and dC's parts [Q, N], ``dcb_t``,
    Σ_p x·dx and d cs [Q, 128] with this tile's heads' lanes filled, d
    cs_last [8, 128] the same)."""
    Q, W = xt.shape
    cd = xt.dtype
    f32 = jnp.float32
    hg = len(cd_j)
    # a float32 run keeps all 24 bits of the lane sums, a bfloat16 one 16
    parts = 3 if cd == f32 else 2
    xf, dy = xt.astype(f32), dy.astype(f32)
    dyb = dy.astype(cd)
    Sb, dSb = S.astype(cd), dS.astype(cd)
    # to_end: Δ exp(cs_last − cs) on the document the chunk ends in
    te = _own([jnp.exp(cs_last[k] - cd_j[k]) for k in range(hg)],
              P) * ending
    thr = _spread_down(through, P)
    # ---- the entering state's part of y: (C · S_inᵀ) ⊙ from_start
    y_in = _dot(Cv, Sb, _NT) * fs
    dyfs = (dy * fs).astype(cd)
    dc = _dot(dyfs, Sb)
    # ---- the state the chunk leaves: S_in·through + (x ⊙ to_end)ᵀ·B
    db = _dot((xf * te).astype(cd), dSb)
    d_to = _dot(Bv, dSb, _NT) * te  # [Q, W]: d x through the state
    # d cs_last, a head: Σ over its tokens of d to_end · to_end and over
    # its state of dS ⊙ S_in · through
    last_t = jnp.sum(d_to * xf, axis=0, keepdims=True)  # [1, W]
    last_s = jnp.sum(dS * S, axis=1, keepdims=True) * thr  # [W, 1]
    head = jax.lax.broadcasted_iota(jnp.int32, (SUBLANE, LANE), 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, (W, 1), 0)
    dlast = jnp.zeros((SUBLANE, LANE), f32)
    for k in range(hg):
        mine_s = (sub >= k * P) & (sub < (k + 1) * P)
        dlast = dlast + jnp.where(
            head == first + k,
            jnp.sum(jnp.where(_own_lanes(k, P, 1, W), last_t, 0.0), axis=1,
                    keepdims=True)
            + jnp.sum(jnp.where(mine_s, last_s, 0.0), axis=0, keepdims=True),
            0.0)
    dS_in = dS * thr + _dot(dyfs, Cv, _TN)
    # ---- within the chunk: [key token j, query token i]
    nb = Q // LANE
    # dy with the other heads' lanes 0: a head's products alone
    dyks = [dyb if hg == 1 else jnp.where(
        _own_lanes(k, P, Q, W), dyb, jnp.zeros_like(dyb)) for k in range(hg)]
    dx, dcb_out = [], []
    for r in range(nb):
        part, dcb = d_to[_rows(r)], list(dcb_t[r])
        for k, dyk in enumerate(dyks):
            for i in range(r, nb):
                e_t = jnp.exp(jnp.minimum(
                    cs_i[k][i] - cd_j[k][_rows(r), :LANE], CLAMP))
                part = part + _dot((e_t * cbm_t[r][i - r]).astype(cd),
                                   dyk[_rows(i)])
                dcb[i - r] = dcb[i - r] + _dot(
                    xt[_rows(r)], dyk[_rows(i)], _NT) * e_t
        dx.append(part)
        dcb_out.append(dcb)
    dx = jnp.concatenate(dx, axis=0) if len(dx) > 1 else dx[0]
    # ---- the sums over a head's lanes. d cs a token: every term of y_i
    # carries exp(cs_i), every term x_j enters carries Δ_j exp(-cs_j);
    # within the chunk both sides are sums of the SAME products dy_i · M_ij
    # · x_j in the operands' own rounding, so they cancel as the [Q, Q]
    # sums would
    x_dx = xf * dx
    sx = _head_sums(x_dx, first, P, parts)
    sc = _head_sums(dyb.astype(f32) * (y - y_in) + dy * y_in - x_dx, first,
                    P, parts)
    return dx, dS_in, db, dc, dcb_out, sx, sc, dlast


def _bwd_kernel(ends_ref, seg_ref, x_ref, b_ref, c_ref, cs_ref, cd_ref,
                pick_ref, s_ref, y_ref, dy_ref, dx_ref, db_ref, dc_ref,
                sx_ref, sc_ref, dlast_ref, dstate, *, P: int, T: int):
    b, zr = pl.program_id(0), pl.program_id(2)
    Q, HW = x_ref.shape[1:]
    Hb = cs_ref.shape[2]
    hg, W = _tile(P)
    nb = Q // LANE
    cd = x_ref.dtype
    f32 = jnp.float32

    @pl.when(zr == 0)
    def _():
        dstate[...] = jnp.zeros(dstate.shape, f32)

    z = pl.num_programs(2) - 1 - zr
    valid = _in_row(z, Q, T)
    seg = seg_ref[0, 0]  # [1, Q]
    segc = _seg_column(seg)
    Bv, Cv = _kept(valid, b_ref[0]), _kept(valid, c_ref[0])
    # transposed: key token j in the sublanes, query token i in the lanes,
    # [r][i - r] the [128, 128] piece of key block r and query block i >= r
    over = (jax.lax.broadcasted_iota(jnp.int32, (LANE, LANE), 0)
            <= jax.lax.broadcasted_iota(jnp.int32, (LANE, LANE), 1))
    mask_t = [[(segc[_rows(r)] == seg[:, _rows(i)].astype(f32))
               & (over if i == r else True) for i in range(r, nb)]
              for r in range(nb)]
    cbm_t = [[jnp.where(mask_t[r][i - r],
                        _dot(Bv[_rows(r)], Cv[_rows(i)], _NT), 0.0)
              for i in range(r, nb)] for r in range(nb)]
    cs, cd_ = cs_ref[0, 0], cd_ref[0, 0]  # [Hb, Q]
    prev, last = ends_ref[b, z], ends_ref[b, z + 1]
    # exp(cs) on the tokens still in the entering document
    columns = _columns(cd_, jnp.where(seg == prev, jnp.exp(cs), 0.0))
    stack = _row_stack(cs, [_through(prev, last, cs), cs[:, -1:]], W)
    ending = _lanes(segc == last.astype(f32), W)

    def cols(t):
        return pl.ds(pl.multiple_of(t * W, W), W)

    def tile(t, carry):
        dcb_t, db, dc, sx, sc, dlast = carry
        xt, y, dy = (r[0, :, cols(t)] for r in (x_ref, y_ref, dy_ref))
        S, dS = s_ref[0, 0, cols(t), :], dstate[cols(t), :]
        rows = _tile_rows(stack, t, hg)
        col = _dot(columns, pick_ref[t], _TN)  # [Q, (hg + 1) · W]
        dx, dS_in, db_t, dc_t, dcb_t, sx_t, sc_t, dlast_t = _bwd_tile(
            t * hg, _kept(valid, xt), _kept(valid, y), _kept(valid, dy), S,
            dS, Bv, Cv, cbm_t, dcb_t,
            [[rows[k:k + 1, _rows(i)] for i in range(nb)]
             for k in range(hg)],
            [col[:, k * W:(k + 1) * W] for k in range(hg)],
            col[:, hg * W:], ending,
            [rows[k:k + 1, Q:Q + 1] for k in range(hg)],
            [rows[k:k + 1, Q + W:] for k in range(hg)], P)
        dx_ref[0, :, cols(t)] = dx.astype(dx_ref.dtype)
        dstate[cols(t), :] = dS_in
        return (dcb_t, db + db_t, dc + dc_t, sx + sx_t, sc + sc_t,
                dlast + dlast_t)

    zeros = lambda *shape: jnp.zeros(shape, f32)  # noqa: E731
    dcb_t, db, dc, sx, sc, dlast = jax.lax.fori_loop(
        0, HW // W, tile,
        ([[zeros(LANE, LANE) for _ in row] for row in cbm_t],
         zeros(*b_ref.shape[1:]), zeros(*c_ref.shape[1:]), zeros(Q, LANE),
         zeros(Q, LANE), zeros(SUBLANE, LANE)))
    dcq = [dc[_rows(i)] for i in range(nb)]
    for r in range(nb):
        acc = db[_rows(r)]
        for i in range(r, nb):
            g = jnp.where(mask_t[r][i - r], dcb_t[r][i - r], 0.0).astype(cd)
            acc = acc + _dot(g, Cv[_rows(i)])
            dcq[i] = dcq[i] + _dot(g, Bv[_rows(r)], _TN)
        db_ref[0, 0, _rows(r), :] = acc
    for i in range(nb):
        dc_ref[0, 0, _rows(i), :] = dcq[i]
    sx_ref[0, 0] = sx[:, :Hb]
    sc_ref[0, 0] = sc[:, :Hb]
    dlast_ref[0, 0, 0] = dlast[:1, :Hb]


def _params(interpret: bool):
    kw = dict(interpret=interpret)
    if not interpret:
        kw["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT)
    return kw


def chunk_cumsum(a: jnp.ndarray, chunk: int) -> jnp.ndarray:
    """[R, T, H] (T whole chunks) -> the inclusive sum over the tokens of
    each chunk."""
    R, T, H = a.shape
    return jnp.cumsum(a.reshape(R, T // chunk, chunk, H), axis=2).reshape(
        R, T, H)


def _operands(x, dt, a, Bm, Cm, seg, chunk: int):
    """The kernels' operands from the caller's, and their dimensions. The
    [T, heads] arrays are made here, padded to whole chunks (a padded
    token: segment 0, Δ = 0) and laid out a head's tokens across the lanes
    ([R, chunks, H, Q]): ``cs`` the cumulative log-decay of each chunk and
    ``cs − log Δ`` (``NEVER`` on padding); last, :func:`column_picks`."""
    R, T, H, P = x.shape
    G, N = Bm.shape[2:]
    Q, Z = chunk, -(-T // chunk)
    hb = heads_per_step(H, P, G)
    J = H // hb  # blocks of heads; hb divides a group's heads
    per_group = (H // G) // hb
    pad = ((0, 0), (0, Z * Q - T))
    seg = jnp.pad(seg.astype(jnp.int32), pad).reshape(R, Z, Q)
    dt, a = (jnp.pad(v, pad + ((0, 0),)) for v in (dt, a))
    cs = chunk_cumsum(a, Q).reshape(R, Z, Q, H)
    dtz = dt.reshape(R, Z, Q, H)
    cd_ = jnp.where(dtz > 0, cs - jnp.log(jnp.where(dtz > 0, dtz, 1.0)),
                    NEVER)
    last = seg[:, :, -1]
    # the document the tokens before chunk z end in, and chunk z's own
    ends = jnp.pad(last, ((0, 0), (1, 0)), constant_values=-1)
    operands = (
        ends, seg.reshape(R, Z, 1, Q), x.reshape(R, T, H * P),
        Bm.reshape(R, T, G * N), Cm.reshape(R, T, G * N),
        cs.transpose(0, 1, 3, 2), cd_.transpose(0, 1, 3, 2),
        column_picks(hb, P),
    )
    return operands, (R, T, H, P, G, N, Q, Z, hb, J, per_group), dt


def _specs(dims, reverse: bool):
    R, T, H, P, G, N, Q, Z, hb, J, per_group = dims

    def at(z):
        return Z - 1 - z if reverse else z

    seg = pl.BlockSpec((1, 1, 1, Q), lambda b, j, z, *_: (b, at(z), 0, 0))
    seq = pl.BlockSpec((1, Q, hb * P), lambda b, j, z, *_: (b, at(z), j))
    grp = pl.BlockSpec((1, Q, N),
                       lambda b, j, z, *_: (b, at(z), j // per_group))
    row = pl.BlockSpec((1, 1, hb, Q), lambda b, j, z, *_: (b, at(z), j, 0))
    st = pl.BlockSpec((1, 1, hb * P, N),
                      lambda b, j, z, *_: (b, at(z), j, 0))
    part = pl.BlockSpec((1, 1, Q, N), lambda b, j, z, *_: (b, j, at(z), 0))
    sums = pl.BlockSpec((1, 1, Q, hb), lambda b, j, z, *_: (b, j, at(z), 0))
    one = pl.BlockSpec((1, 1, 1, 1, hb),
                       lambda b, j, z, *_: (b, j, at(z), 0, 0))
    hg, W = _tile(P)
    picks = pl.BlockSpec((hb // hg, 6 * hb, (hg + 1) * W),
                         lambda b, j, z, *_: (0, 0, 0))
    return seg, seq, grp, row, picks, st, part, sums, one


def scan_fwd(x, dt, a, Bm, Cm, seg, chunk: int, keep: bool = False,
             interpret: bool = False):
    """x [R, T, H, P] (the compute dtype); dt [R, T, H] float32 (Δ, after
    softplus) and a = Δ·A, the log-decays; Bm, Cm [R, T, G, N]; seg [R, T]
    int; T any length. Returns (y [R, T, H, P] float32, the state entering
    each chunk [R, chunks, H · P, N] float32 or, without ``keep``, None)."""
    operands, dims, _ = _operands(x, dt, a, Bm, Cm, seg, chunk)
    R, T, H, P, G, N, Q, Z, hb, J, _ = dims
    segs, seq, grp, row, picks, st, *_ = _specs(dims, reverse=False)
    f32 = jnp.float32
    out_specs = [seq] + ([st] if keep else [])
    out_shape = [jax.ShapeDtypeStruct((R, T, H * P), f32)] + (
        [jax.ShapeDtypeStruct((R, Z, H * P, N), f32)] if keep else [])
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, P=P, T=T, keep=keep),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(R, J, Z),
            in_specs=[segs, seq, grp, grp, row, row, picks],
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((hb * P, N), f32)]),
        out_shape=out_shape, name=FWD_NAME, **_params(interpret),
    )(*operands)
    return out[0].reshape(R, T, H, P), (out[1] if keep else None)


def scan_bwd(x, dt, a, Bm, Cm, seg, states, y, dy, chunk: int,
             interpret: bool = False):
    """Gradients (dx as x; dΔ, da [R, T, H] float32; dB, dC [R, T, G, N]
    float32) from the forward's operands, its entering states, y and dy."""
    operands, dims, dt_p = _operands(x, dt, a, Bm, Cm, seg, chunk)
    R, T, H, P, G, N, Q, Z, hb, J, per_group = dims
    segs, seq, grp, row, picks, st, part, sums, one = _specs(dims,
                                                             reverse=True)
    f32 = jnp.float32
    dx, db, dc, sx, sc, dlast = pl.pallas_call(
        functools.partial(_bwd_kernel, P=P, T=T),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(R, J, Z),
            in_specs=[segs, seq, grp, grp, row, row, picks, st, seq, seq],
            out_specs=[seq, part, part, sums, sums, one],
            scratch_shapes=[pltpu.VMEM((hb * P, N), f32)]),
        out_shape=[jax.ShapeDtypeStruct((R, T, H * P), x.dtype),
                   jax.ShapeDtypeStruct((R, J, T, N), f32),
                   jax.ShapeDtypeStruct((R, J, T, N), f32),
                   jax.ShapeDtypeStruct((R, J, Z * Q, hb), f32),
                   jax.ShapeDtypeStruct((R, J, Z * Q, hb), f32),
                   jax.ShapeDtypeStruct((R, J, Z, 1, hb), f32)],
        name=BWD_NAME, **_params(interpret),
    )(*operands, states, y.reshape(R, T, H * P), dy.reshape(R, T, H * P))

    def up(v):  # [R, J, Z · Q, hb] -> [R, Z, Q, H]
        return v.transpose(0, 2, 1, 3).reshape(R, Z, Q, H)

    dlast = dlast.transpose(0, 2, 3, 1, 4).reshape(R, Z, 1, H)
    dcs = up(sc).at[:, :, Q - 1:, :].add(dlast)
    # the cumulative sum's transpose — a token's own and every later one
    # of its chunk — as the chunk's total less the sum before it
    da = (jnp.sum(dcs, axis=2, keepdims=True) - jnp.cumsum(dcs, axis=2)
          + dcs).reshape(R, Z * Q, H)[:, :T]
    # Σ_p x·dx a token and head carries Δ: dΔ's own part is that over Δ
    sx = up(sx).reshape(R, Z * Q, H)
    ddt = jnp.where(dt_p > 0, sx / jnp.where(dt_p > 0, dt_p, 1.0),
                    0.0)[:, :T]

    def groups(part):  # the blocks of heads of a group, summed
        return part.reshape(R, G, per_group, T, N).sum(axis=2).transpose(
            0, 2, 1, 3)

    return dx.reshape(R, T, H, P), ddt, da, groups(db), groups(dc)
