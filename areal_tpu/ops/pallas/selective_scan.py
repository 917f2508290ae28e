"""TPU selective scan (S6, Mamba-1) for packed segment batches.

    h_t = keep_t · exp(Δ_t ⊙ A) ⊙ h_{t-1} + (Δ_t ⊙ x_t) ⊗ B_t
    y_t = h_t · C_t

``A`` is ``[d_inner, N]``: the decay is a channel's AND a state's own, so
there is no ``C·Bᵀ`` product for the MXU (Mamba-2's SSD form needs one
scalar decay a head): it is a first-order recurrence over ``d_inner x N``
values a token, on the VPU. The kernels walk a row's tokens in chunks of
``CHUNK``; the state of a tile of ``D_TILE`` channels, ``[N, D_TILE]``
float32 (states in the sublanes, channels in the lanes), stays in VMEM
from chunk to chunk, so no ``[T, d_inner, N]`` array exists in either
pass:

 - forward: grid (rows, chunks, channel tiles). Writes ``y`` and the state
   ENTERING each chunk (``[rows, chunks, N, d_inner]`` float32: 1/CHUNK of
   the whole history), which is all the backward pass reads of it;
 - backward: the same grid with the chunks reversed. A cell re-runs its
   chunk's recurrence from the entering state into a ``[CHUNK + 1, N,
   D_TILE]`` scratch, then walks the chunk backwards with the gradient of
   the state as its carry. ``dB_t`` and ``dC_t`` sum over channels — over
   lanes, and over the channel tiles, which are therefore the innermost
   grid axis: a cell folds its tile to 128 lanes a token into a scratch
   that the chunk's last tile reduces.

``B_t`` and ``C_t`` multiply along the sublanes. They come in as
``[rows, T, N, 1]`` (a token's 16 values down a column), so that
``b_ref[t]`` is a ``[N, 1]`` column that broadcasts along the lanes — a
row ``[1, N]`` would need a transpose a token. ``keep`` (0 at a
document's first token, which resets the state; 1 elsewhere) is a scalar
a token, prefetched to SMEM.

The kernels' device ops are named ``s6_scan_fwd`` / ``s6_scan_bwd``.
CPU/testing: ``interpret=True``; tests/test_tpu_compile.py compiles them
for a described v5e.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
# Tokens a grid cell walks, and channels it holds: [CHUNK + 1, N, D_TILE]
# float32 is the backward's scratch (4.3 MB at N = 16); with the column
# blocks of B, C, dB and dC (a token's 16 values take a whole [16, 128]
# tile: 0.5 MB a block, twice buffered) the backward kernel holds 12.5 MB
# of VMEM — 128 x 512 does not fit the 16 MB a kernel is given.
CHUNK = 64
D_TILE = 1024
# Tokens of a chunk unrolled into one loop body: a token's decay (a
# multiply and an exp) does not wait for the token before it, only the
# state does, and the compiler overlaps what it sees in one body.
# Measured on a TPU v5e at 1 x 8192 x 5120 x 16 (tools/sambay_sweep.py;
# forward / forward + backward, ms; PERF.md §5, PR 42), 64 x 512: unroll
# 1 10.17 / 28.89, 4 4.68 / 13.64, 8 4.03 / 11.89, 16 3.88 / 10.93; at
# unroll 8: 32 x 512 4.34 / 12.50, 64 x 1024 3.57 / 10.56.
UNROLL = 8

FWD_NAME, BWD_NAME = "s6_scan_fwd", "s6_scan_bwd"


def tile_of(d_inner: int) -> int:
    """Channels a cell holds: ``D_TILE`` where it divides ``d_inner``,
    else the largest multiple of 128 below it that does."""
    for t in range(min(D_TILE, d_inner), 0, -LANE):
        if d_inner % t == 0 and t % LANE == 0:
            return t
    raise ValueError(f"d_inner={d_inner} is no multiple of {LANE}")


def supported(d_inner: int) -> bool:
    """Channels in whole lanes (the caller pads a row to whole chunks)."""
    return d_inner % LANE == 0


def _loop(body, init):
    """``fori_loop`` over a chunk's tokens, ``UNROLL`` of them a loop step
    (Mosaic unrolls a loop whole or not at all)."""
    def steps(i, carry):
        for u in range(UNROLL):
            carry = body(i * UNROLL + u, carry)
        return carry

    assert CHUNK % UNROLL == 0
    return jax.lax.fori_loop(0, CHUNK // UNROLL, steps, init)


def _fold_lanes(v: jnp.ndarray) -> jnp.ndarray:
    """[N, D] -> [N, 128]: the sum of D's 128-lane tiles."""
    out = v[:, :LANE]
    for k in range(1, v.shape[1] // LANE):
        out = out + v[:, k * LANE:(k + 1) * LANE]
    return out


def _fwd_kernel(keep_ref, x_ref, dt_ref, b_ref, c_ref, a_ref,
                y_ref, h0_ref, h_scr):
    b, c, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(c == 0)
    def _():
        h_scr[j] = jnp.zeros(h_scr.shape[1:], jnp.float32)

    h = h_scr[j]
    h0_ref[0, 0] = h
    A = a_ref[...]
    base = c * CHUNK

    def step(t, h):
        dt = dt_ref[0, pl.ds(t, 1), :]  # [1, D]
        x = x_ref[0, pl.ds(t, 1), :]
        keep = keep_ref[b, base + t].astype(jnp.float32)
        a = jnp.exp(dt * A) * keep  # [N, D]
        h = a * h + (dt * x) * b_ref[0, t]
        y_ref[0, pl.ds(t, 1), :] = jnp.sum(h * c_ref[0, t], axis=0,
                                           keepdims=True)
        return h

    h_scr[j] = _loop(step, h)


def _bwd_kernel(keep_ref, x_ref, dt_ref, b_ref, c_ref, a_ref, h0_ref,
                dy_ref, dx_ref, ddt_ref, db_ref, dc_ref, da_ref,
                g_scr, hs_scr, pb_scr, pc_scr):
    b, cr, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n_chunks, n_tiles = pl.num_programs(1), pl.num_programs(2)
    base = (n_chunks - 1 - cr) * CHUNK

    @pl.when(cr == 0)
    def _():
        g_scr[j] = jnp.zeros(g_scr.shape[1:], jnp.float32)

    @pl.when((b == 0) & (cr == 0))
    def _():
        da_ref[j] = jnp.zeros(da_ref.shape[1:], jnp.float32)

    @pl.when(j == 0)
    def _():
        pb_scr[...] = jnp.zeros(pb_scr.shape, jnp.float32)
        pc_scr[...] = jnp.zeros(pc_scr.shape, jnp.float32)

    A = a_ref[...]

    def decay(t):
        dt = dt_ref[0, pl.ds(t, 1), :]
        keep = keep_ref[b, base + t].astype(jnp.float32)
        return dt, jnp.exp(dt * A) * keep

    # the chunk's states again: hs[t] is the state BEFORE token t
    hs_scr[0] = h0_ref[0, 0]

    def again(t, h):
        dt, a = decay(t)
        h = a * h + (dt * x_ref[0, pl.ds(t, 1), :]) * b_ref[0, t]
        hs_scr[t + 1] = h
        return h

    _loop(again, hs_scr[0])

    def back(i, carry):
        g, dA = carry
        t = CHUNK - 1 - i
        dt, a = decay(t)
        x = x_ref[0, pl.ds(t, 1), :]
        dy = dy_ref[0, pl.ds(t, 1), :]
        bt = b_ref[0, t]
        g = g + c_ref[0, t] * dy  # dL/dh_t, whole
        pc_scr[t] = pc_scr[t] + _fold_lanes(hs_scr[t + 1] * dy)
        pb_scr[t] = pb_scr[t] + _fold_lanes(g * (dt * x))
        s = jnp.sum(g * bt, axis=0, keepdims=True)  # [1, D]
        e = g * hs_scr[t] * a  # dL/d(Δ_t A)
        dx_ref[0, pl.ds(t, 1), :] = s * dt
        ddt_ref[0, pl.ds(t, 1), :] = s * x + jnp.sum(e * A, axis=0,
                                                    keepdims=True)
        return a * g, dA + e * dt

    g, dA = _loop(back, (g_scr[j], jnp.zeros(A.shape, jnp.float32)))
    g_scr[j] = g
    da_ref[j] = da_ref[j] + dA

    @pl.when(j == n_tiles - 1)
    def _():
        db_ref[0] = jnp.sum(pb_scr[...], axis=-1, keepdims=True)
        dc_ref[0] = jnp.sum(pc_scr[...], axis=-1, keepdims=True)


def _specs(T: int, N: int, dt_: int, reverse: bool):
    Z = T // CHUNK

    def chunk(c):
        return Z - 1 - c if reverse else c

    seq = pl.BlockSpec((1, CHUNK, dt_), lambda b, c, j, *_: (b, chunk(c), j))
    col = pl.BlockSpec((1, CHUNK, N, 1),
                       lambda b, c, j, *_: (b, chunk(c), 0, 0))
    a = pl.BlockSpec((N, dt_), lambda b, c, j, *_: (0, j))
    h0 = pl.BlockSpec((1, 1, N, dt_),
                      lambda b, c, j, *_: (b, chunk(c), 0, j))
    return seq, col, a, h0


def _params(interpret: bool):
    kw = dict(interpret=interpret)
    if not interpret:
        kw["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3)
    return kw


@functools.partial(jax.jit, static_argnames=("interpret",))
def scan_fwd(x, dt, A, Bm, Cm, keep, interpret: bool = False,
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x, dt [R, T, D] float32; A [D, N]; Bm, Cm [R, T, N]; keep [R, T]
    int32. Returns (y [R, T, D] float32, the state entering each chunk
    [R, T / CHUNK, N, D] float32)."""
    R, T, D = x.shape
    N = A.shape[1]
    dt_ = tile_of(D)
    J, Z = D // dt_, T // CHUNK
    seq, col, a, h0 = _specs(T, N, dt_, reverse=False)
    f32 = jnp.float32
    return pl.pallas_call(
        _fwd_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(R, Z, J),
            in_specs=[seq, seq, col, col, a],
            out_specs=[seq, h0],
            scratch_shapes=[pltpu.VMEM((J, N, dt_), f32)]),
        out_shape=[jax.ShapeDtypeStruct((R, T, D), f32),
                   jax.ShapeDtypeStruct((R, Z, N, D), f32)],
        name=FWD_NAME, **_params(interpret),
    )(keep.astype(jnp.int32), x.astype(f32), dt.astype(f32),
      Bm.astype(f32)[..., None], Cm.astype(f32)[..., None],
      A.astype(f32).T)


@functools.partial(jax.jit, static_argnames=("interpret",))
def scan_bwd(x, dt, A, Bm, Cm, keep, h0, dy, interpret: bool = False):
    """Gradients (dx, ddt [R, T, D]; dA [D, N]; dB, dC [R, T, N]), all
    float32, from the forward's inputs, its entering states and dy."""
    R, T, D = x.shape
    N = A.shape[1]
    dt_ = tile_of(D)
    J, Z = D // dt_, T // CHUNK
    seq, col, a, h0_spec = _specs(T, N, dt_, reverse=True)
    f32 = jnp.float32
    da = pl.BlockSpec((J, N, dt_), lambda b, c, j, *_: (0, 0, 0))
    dx, ddt, db, dc, dA = pl.pallas_call(
        _bwd_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(R, Z, J),
            in_specs=[seq, seq, col, col, a, h0_spec, seq],
            out_specs=[seq, seq, col, col, da],
            scratch_shapes=[pltpu.VMEM((J, N, dt_), f32),
                            pltpu.VMEM((CHUNK + 1, N, dt_), f32),
                            pltpu.VMEM((CHUNK, N, LANE), f32),
                            pltpu.VMEM((CHUNK, N, LANE), f32)]),
        out_shape=[jax.ShapeDtypeStruct((R, T, D), f32),
                   jax.ShapeDtypeStruct((R, T, D), f32),
                   jax.ShapeDtypeStruct((R, T, N, 1), f32),
                   jax.ShapeDtypeStruct((R, T, N, 1), f32),
                   jax.ShapeDtypeStruct((J, N, dt_), f32)],
        name=BWD_NAME, **_params(interpret),
    )(keep.astype(jnp.int32), x.astype(f32), dt.astype(f32),
      Bm.astype(f32)[..., None], Cm.astype(f32)[..., None],
      A.astype(f32).T, h0, dy.astype(f32))
    dA = dA.transpose(1, 0, 2).reshape(N, D).T
    return dx, ddt, dA, db[..., 0], dc[..., 0]
