"""TPU grouped-head attention for packed segment batches: causal
self-attention over one packed row, full or under a sliding window.

A query at position p of its document sees the keys of the same document
up to p — all of them, or under a ``window`` those at p-window+1 .. p
(``ops/attention.segment_mask``'s rule; packing keeps a document
contiguous in its row, so the distance inside the document is the
distance in the row). The hot op is jax's splash-attention kernel
(``jax.experimental.pallas.ops.tpu.splash_attention``) in its MQA form,
under a ``CausalMask`` or a ``LocalMask`` — the grid of a windowed call
holds only the key blocks the window touches. Of the blocks the static
mask leaves, a query block runs only those its own documents reach
(:func:`blocks_needed`, from the row's segment ids): a key block that
holds only OTHER documents, and every key block of a query block that is
nothing but padding, is skipped — no matmul, no exponential, no DMA —
in the forward, the dKV and the dQ kernels alike. The kernel's
scalar-prefetched ``block_mask`` / ``data_next`` are computed in the
graph for that (:func:`_narrowed`); a skipped block's scores were all
masked and added exact zeros, so outputs and gradients of real tokens are
bit for bit what the static schedule gives. A row of ONE block keeps the
static schedule (nothing to skip; a batch of such rows then stays one
kernel call). Wrapped with areal_tpu's packed-batch semantics:

 - inputs are [B, T, H, D]; self-attention only (queries and keys of one
   packed row);
 - GQA runs the kernel's MQA form once a key/value head (vmapped over
   rows and key/value heads): K and V are NOT repeated, and dK / dV come
   back at their own head count;
 - document masking via the kernel's segment ids, 0 = padding; a padding
   query's row comes back as zeros — inside the kernel it holds what the
   query attended of the padding before it (a block with real tokens) or
   ``0 * (1 / 0)`` (a block of nothing but padding, which ran no key
   block: its softmax statistic is -inf, and no backward block reads it);
 - head_dim is padded up to the lane width (128) when needed, and the row
   up to a multiple of the tile of :func:`pick_tile`, with segment id 0.

The kernels' device ops are named ``splash_mqa_{fwd,dkv,dq}_segmented_*``
(a full-causal call runs no ``dq``: its backward is the one fused ``dkv``
kernel). Per compiled step,
:func:`geometry_counts` says which (length, padded length, tile, window)
each WINDOWED call was traced with, and how many key blocks its static
mask visits against a causal kernel's; :func:`causal_geometry_counts`
which (length, padded length, tile) each full-causal call was traced
with; :func:`needed_counts` what the packed grids the engine ran needed of
those static blocks (counted on the host, by :func:`count_needed`).

CPU/testing: ``interpret=True`` runs the kernels in Pallas's plain
interpreter (tests/test_window_attention.py) — the TPU interpreter of
``pltpu.force_tpu_interpret_mode()`` does not take a vmapped grid;
tests/test_tpu_compile.py compiles the kernels for a described v5e.
"""

from __future__ import annotations

import collections
import functools
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as _splash,
)
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_mask as _mask,
)

from areal_tpu.ops import attention as _attention

# The TPU's lane width: heads are padded to it, and a row the kernel takes
# is a multiple of it.
LANE = 128
# The device scope around the kernel and its layout glue, inside the
# transformer's "attention" (base/telemetry.WINDOW_SCOPES): a windowed
# call's, and a full-causal call's.
SCOPE = "window_attention"
CAUSAL_SCOPE = "causal_attention"
# jax.ad_checkpoint name of what the kernel's backward reads of its
# forward (its output and softmax statistics): the layer scan's
# "attention" remat entry keeps it (models/transformer.py).
RESIDUALS = "window_attention_residuals"

# c(t): ns per row · query token · VISITED key token that the kernels of
# one training step (3 forward passes, dKV, dQ) take at tile t — measured
# on a TPU v5e, the kernels alone, 1 row x 8192 tokens in one document,
# window 1024, 32 query / 4 key-value heads of 128, bf16
# (tools/window_tile_sweep.py; PERF.md §5, PR 32). pick_tile uses only
# their ratios. A tile of 2048 does not fit the chip's fast memory.
TILE_COST = {1024: 0.8599, 512: 1.0016, 256: 1.9394}
# The same for a full-causal call (no window): ns per row · query token ·
# visited key token of 2 forward passes + 1 forward-and-backward THROUGH
# THE WRAPPER (its layout glue included), at the blocks of
# :func:`_block_sizes` — measured on a TPU v5e, 1 row x 6144 tokens in one
# document, 14 query / 2 key-value heads of 64 in 128 lanes, bf16
# (tools/window_tile_sweep.py --window 0; PERF.md §5, PR 45); the ratios
# held at 3072 and 7680 tokens, at 32 / 4 and 16 / 16 heads of 128. Tile
# 128 is its ratio to tile 512 at 8 rows x 512 tokens (4.77), the one
# shape it was timed at.
CAUSAL_TILE_COST = {1024: 0.2804, 768: 0.3314, 512: 0.3593, 256: 0.8073,
                    128: 1.7130}
# A causal call's key block is computed 512 keys at a time where that
# divides it (tile 1024: 5-6 % cheaper than whole).
_CAUSAL_KV_COMPUTE = 512
# Heads wider than the lane width (qwen3_next: 256) run at tiles up to
# this: at tile 1024 the backward of 8 grouped query heads of 256 asks for
# more than the chip's 16 MB of scoped VMEM — the fused kernel for 17.7 MB
# at a row of 14,336 (my chip run, PR 52), the dKV kernel alone at some
# lengths (11,776: a compile for a described v5e). The tables above were
# measured at heads of 64 and 128; their RATIOS are used at 256 too,
# unmeasured there (ROADMAP R6).
_WIDE_HEAD_MAX_TILE = 512


def _round_up(n: int, tile: int) -> int:
    return -(-n // tile) * tile


def _static_blocks(n: int, tile: int, window: Optional[int]) -> np.ndarray:
    """bool [n, n]: the blocks the static mask leaves — query block i
    reaches back to key i*tile - window + 1, or with no window to key 0."""
    i = np.arange(n)[:, None]
    j = i.T
    if window is None:
        return j <= i
    return (j <= i) & (j >= np.maximum(i * tile - window + 1, 0) // tile)


def blocks_visited(n_pad: int, tile: int,
                   window: Optional[int]) -> Tuple[int, int]:
    """(key blocks the static mask of a call over a padded row of
    ``n_pad`` tokens visits at ``tile``, key blocks a causal mask would)."""
    n = n_pad // tile
    return int(_static_blocks(n, tile, window).sum()), n * (n + 1) // 2


def blocks_needed(segment_ids, tile: int, window: Optional[int] = None):
    """bool [..., n, n] over the ``tile``-token blocks of a packed row
    (``segment_ids`` [..., n * tile], 0 = padding; numpy or traced): query
    block i needs key block j when j is in the static mask's range for i
    (:func:`blocks_visited`'s) and a token of i that is not padding
    belongs to a document with a token in j. A document is contiguous in
    its row, so the earliest document of block i — its first real token's
    — reaches back into block j < i exactly when block j ENDS in it: the
    needed blocks are one range up to i. A query block of nothing but
    padding needs no block."""
    xp = jnp if isinstance(segment_ids, jax.Array) else np
    rows = segment_ids.reshape(*segment_ids.shape[:-1], -1, tile)
    real = rows > 0
    first = xp.take_along_axis(  # [..., n, 1]
        rows, xp.argmax(real, axis=-1)[..., None], axis=-1)
    last = rows[..., None, :, -1]  # [..., 1, n]
    n = rows.shape[-2]
    return (_static_blocks(n, tile, window) & real.any(axis=-1)[..., None]
            & (np.eye(n, dtype=bool) | (last == first)))


def pick_tile(n: int, window: Optional[int] = None,
              head_dim: int = LANE) -> int:
    """The tile a row of n tokens runs: the one whose visited blocks at
    the padded length cost least, ``visited · t² · c(t)``; ties to the
    larger; at heads wider than the lanes, of the tiles that fit."""
    costs = CAUSAL_TILE_COST if window is None else TILE_COST
    if head_dim > LANE:
        costs = {t: c for t, c in costs.items() if t <= _WIDE_HEAD_MAX_TILE}

    def cost(t):
        visited, _ = blocks_visited(_round_up(n, t), t, window)
        return (visited * t * t * costs[t], -t)

    return min(costs, key=cost)


def padded_len(n: int, window: Optional[int] = None,
               head_dim: int = LANE) -> Optional[int]:
    """The padded length the kernel runs a row of n tokens at; None when
    n is not a multiple of 128 (the caller takes the reference)."""
    if n % LANE:
        return None
    return _round_up(n, pick_tile(n, window, head_dim))


# Which (length, padded length, tile, window) each WINDOWED call TRACED
# with, by the label of the compiled step (ops/attention.dispatch_label),
# and the key blocks it visits / a causal kernel would:
# {label: {(n, n_pad, tile, window): [calls, visited, causal]}}.
_GEOMETRY: Dict[str, Dict[Tuple[int, int, int, int], list]] = (
    collections.defaultdict(dict))
# Which (length, padded length, tile) each FULL-CAUSAL call traced with —
# a count of its own: readers of the windowed one take its calls for a
# sliding layer's. {label: {(n, n_pad, tile): calls}}.
_CAUSAL_GEOMETRY: Dict[str, collections.Counter] = collections.defaultdict(
    collections.Counter)


# What the packed grids a step ran needed of the static mask's blocks, by
# grid — counted on the HOST from the packer's layouts (count_needed):
# {(rows, n, n_pad, tile, window or 0): [grids, needed, static]}.
_NEEDED: Dict[Tuple[int, int, int, int, int], list] = {}


def geometry_counts() -> Dict[str, Dict[Tuple[int, int, int, int], Dict]]:
    return {
        label: {g: dict(zip(("calls", "blocks_visited", "blocks_causal"), c))
                for g, c in geoms.items()}
        for label, geoms in _GEOMETRY.items()
    }


def causal_geometry_counts() -> Dict[str, Dict[Tuple[int, int, int], int]]:
    return {label: dict(c) for label, c in _CAUSAL_GEOMETRY.items()}


def count_needed(segment_ids: np.ndarray,  # [R, L] on the host
                 window: Optional[int] = None,
                 head_dim: int = LANE) -> Tuple[int, int]:
    """(key blocks the rows of a packed grid need, key blocks the static
    mask visits) at the tile and padded length :func:`window_attention`
    runs them at — the same :func:`blocks_needed` the kernel's schedule is
    narrowed by; a row of ONE block keeps the static schedule. Added to
    :func:`needed_counts` under the grid's shape."""
    R, L = segment_ids.shape
    tile = pick_tile(L, window, head_dim)
    n_pad = _round_up(L, tile)
    visited = needed = R * blocks_visited(n_pad, tile, window)[0]
    if n_pad > tile:
        needed = int(blocks_needed(
            np.pad(segment_ids, [(0, 0), (0, n_pad - L)]), tile,
            window).sum())
    c = _NEEDED.setdefault((R, L, n_pad, tile, window or 0), [0, 0, 0])
    c[0] += 1
    c[1] += needed
    c[2] += visited
    return needed, visited


def needed_counts() -> Dict[Tuple[int, int, int, int, int], Dict]:
    """{(rows, length, padded length, tile, window or 0): {grids counted,
    blocks_needed, blocks_static}} of the grids :func:`count_needed` saw."""
    return {g: dict(zip(("grids", "blocks_needed", "blocks_static"), c))
            for g, c in _NEEDED.items()}


def _count(n: int, n_pad: int, tile: int, window: Optional[int]) -> None:
    label = _attention.active_label()
    if window is None:
        _CAUSAL_GEOMETRY[label][(n, n_pad, tile)] += 1
        return
    visited, causal = blocks_visited(n_pad, tile, window)
    c = _GEOMETRY[label].setdefault((n, n_pad, tile, window), [0, 0, 0])
    c[0] += 1
    c[1] += visited
    c[2] += causal


def _block_sizes(tile: int, window: Optional[int],
                 head_dim: int = LANE) -> _splash.BlockSizes:
    """The kernels' blocks at ``tile``, as each table was measured: square
    and computed whole, dKV and dQ kernels, under a window; under a causal
    mask the key block computed ``_CAUSAL_KV_COMPUTE`` keys at a time and
    ONE fused backward kernel (5 matmuls a block pair where dKV + dQ do
    7; dQ leaves it a partial sum a key block, in the compute dtype, and
    is added up outside — under a window its grid would hold every causal
    block, so the windowed call keeps the two kernels). These fix the
    GRID; which of its steps run is the static mask's schedule narrowed by
    the row's segment ids (:func:`_narrowed`): a skipped step costs a grid
    step and no DMA, and the fused backward still writes that step's dQ
    partial (zeros)."""
    if window is not None or head_dim > LANE:
        # heads wider than the lanes keep the two kernels under a causal
        # mask too: the fused kernel's dQ partial sums, one a key block,
        # are [key blocks, heads, row, head_dim] — 4.3 GB at 16 heads of
        # 256 over a row of 16,384 at tile 512
        return _splash.BlockSizes(
            block_q=tile, block_kv=tile, block_kv_compute=tile,
            block_q_dkv=tile, block_kv_dkv=tile, block_kv_dkv_compute=tile,
            block_q_dq=tile, block_kv_dq=tile,
        )
    compute = tile if tile % _CAUSAL_KV_COMPUTE else _CAUSAL_KV_COMPUTE
    return _splash.BlockSizes(
        block_q=tile, block_kv=tile, block_kv_compute=compute,
        block_q_dkv=tile, block_kv_dkv=tile, block_kv_dkv_compute=compute,
        use_fused_bwd_kernel=True,
    )


class _Walk(NamedTuple):
    """One kernel's static schedule a grid step at a time, in the order
    its grid walks (a forward / dQ grid a query block at a time, a dKV
    grid a key block at a time): does the step run, its ``block_mask``
    entry, the (query, key) block pair it is — an index into
    ``blocks_needed(...).reshape(-1)`` — and ``key``, the step's number
    and its ``data_next`` entry in one integer, ``step * base + entry``."""
    live: np.ndarray
    block: np.ndarray
    pair: np.ndarray
    key: np.ndarray
    base: int
    by_key: bool


def _walk(info, by_key: bool) -> Optional[_Walk]:
    """An entry (r, c) of a forward / dQ mask info is query block r
    against the key block its ``data_next`` names; of a dKV info
    (``by_key``) the query block its ``data_next`` names against key
    block c (the other side of either may be shrunk to the blocks the
    static mask visits)."""
    if info is None:
        return None
    block, data = (np.asarray(x)[0].astype(np.int32)
                   for x in (info.block_mask, info.data_next))
    r, c = np.indices(block.shape)
    qi, ki = (data, c) if by_key else (r, data)
    n = block.shape[1 if by_key else 0]  # blocks a side
    order = (lambda x: x.T.reshape(-1)) if by_key else (
        lambda x: x.reshape(-1))
    live = order(block > 0)
    return _Walk(live, order(block), np.where(live, order(qi * n + ki), 0),
                 np.arange(block.size) * n + order(data), n, by_key)


@functools.lru_cache(maxsize=None)
def _kernel(n_pad: int, window: Optional[int], group: int,
            sizes: _splash.BlockSizes, interpret: bool = False):
    """(the splash MQA kernel of one key/value head, the walks of its
    forward, dQ and dKV schedules): ``group`` query heads over a row of
    ``n_pad`` tokens in blocks of ``sizes``, causality (and the window: a
    ``LocalMask`` reaching ``window - 1`` back and 0 ahead) in the mask.
    Made once a geometry; its mask infos — the static block schedule —
    are concrete arrays also when made under a trace."""
    shape = (n_pad, n_pad)
    mask = (_mask.CausalMask(shape) if window is None
            else _mask.LocalMask(shape, (window - 1, 0), 0))
    with jax.ensure_compile_time_eval():
        kernel = _splash.make_splash_mqa_single_device(
            _mask.MultiHeadMask([mask] * group), block_sizes=sizes,
            residual_checkpoint_name=RESIDUALS, interpret=interpret,
        )
    return kernel, (_walk(kernel.fwd_mask_info, False),
                    _walk(kernel.dq_mask_info, False),
                    _walk(kernel.dkv_mask_info, True))


def _narrow(walk: _Walk, needed: jnp.ndarray, like):
    """(``block_mask``, ``data_next``) of one kernel, of the static
    ``like``'s shape [1, rows, columns] and types: its schedule with the
    entries of the blocks that ``needed`` (:func:`blocks_needed`, traced:
    [n, n]) leaves out zeroed, and every grid step fetching the block of
    the next step that runs (past the last one: of the first)."""
    past = len(walk.live) * walk.base
    run = walk.live & jnp.take(needed.reshape(-1), walk.pair)
    nxt = jax.lax.cummin(jnp.where(run, walk.key, past), reverse=True)
    nxt = jnp.where(nxt == past, jax.lax.index_in_dim(nxt, 0), nxt)
    shape = like.block_mask.shape

    def back(x, dtype):  # the walk's order -> [1, rows, columns]
        x = x.astype(dtype)
        if walk.by_key:  # the walk went down the columns
            return x.reshape(shape[0], shape[2], shape[1]).swapaxes(1, 2)
        return x.reshape(shape)

    return (back(jnp.where(run, walk.block, 0), like.block_mask.dtype),
            back(nxt % walk.base, like.data_next.dtype))


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _schedules(segment_ids: jnp.ndarray,  # [n_pad]: one packed row
               n_pad, window, group, sizes, interpret):
    """The narrowed (``block_mask``, ``data_next``) of the geometry's
    forward, dQ (None under the fused backward) and dKV kernels. A program
    of its own, traced once a geometry: the callers' traces (a layer's
    forward, its backward, its recomputation, a program after the other)
    bind it as one equation."""
    kernel, walks = _kernel(n_pad, window, group, sizes, interpret)
    needed = blocks_needed(segment_ids, sizes.block_q, window)
    infos = (kernel.fwd_mask_info, kernel.dq_mask_info, kernel.dkv_mask_info)
    return tuple(None if walk is None else _narrow(walk, needed, info)
                 for info, walk in zip(infos, walks))


def _narrowed(segment_ids: jnp.ndarray, *geometry):
    """The geometry's kernel (:func:`_kernel`'s arguments) with the
    schedules of its forward and backward kernels narrowed to the blocks
    the row needs: the mask function and the segment ids still mask
    INSIDE a block, as under the static schedule."""
    kernel, _ = _kernel(*geometry)
    infos = (kernel.fwd_mask_info, kernel.dq_mask_info, kernel.dkv_mask_info)
    return _splash.SplashAttentionKernel(
        *(info and info._replace(block_mask=now[0], data_next=now[1])
          for info, now in zip(infos, _schedules(segment_ids, *geometry))),
        **kernel.kwargs)


@functools.partial(jax.named_call, name="pallas_window_attention")
def window_attention(
    q: jnp.ndarray,  # [B, T, Hq, D]
    k: jnp.ndarray,  # [B, T, Hkv, D]
    v: jnp.ndarray,  # [B, T, Hkv, D]
    q_segment_ids: jnp.ndarray,  # [B, T] int, 0 = pad
    kv_segment_ids: jnp.ndarray,  # [B, T]
    window: Optional[int] = None,  # None: full causal
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    B, T, Hq, D = q.shape
    Hkv = k.shape[2]
    if k.shape[1] != T:
        raise ValueError("window_attention is self-attention over one "
                         f"packed row: T={T}, S={k.shape[1]}")
    if T % LANE:
        raise ValueError(
            f"row length T={T} is no multiple of 128; "
            "ops/attention.packed_attention routes such shapes to the "
            "reference")
    tile = pick_tile(T, window, D)
    T_pad = _round_up(T, tile)
    _count(T, T_pad, tile, window)
    if scale is None:
        scale = D ** -0.5
    G = Hq // Hkv

    # [B, T, H, D] -> [B, Hkv, G, T, D] / [B, Hkv, T, D], heads padded to
    # the lane width and the row to its tile; the kernel takes no scale.
    lanes, more = max(LANE - D, 0), T_pad - T

    def pad(x):  # [..., L, D]
        if not (more or lanes):
            return x
        return jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(0, more), (0, lanes)])

    def pad_ids(ids):  # [B, L]
        return jnp.pad(ids, [(0, 0), (0, more)]) if more else ids

    qt = pad((q * jnp.asarray(scale, q.dtype)).reshape(
        B, T, Hkv, G, D).transpose(0, 2, 3, 1, 4))
    kt = pad(k.transpose(0, 2, 1, 3))
    vt = pad(v.transpose(0, 2, 1, 3))
    seg = _splash.SegmentIds(q=pad_ids(q_segment_ids).astype(jnp.int32),
                             kv=pad_ids(kv_segment_ids).astype(jnp.int32))

    geometry = (T_pad, window, G, _block_sizes(tile, window, D), interpret)

    def row(q, k, v, seg):  # one packed row, a key/value head at a time
        # A row of ONE block has nothing to skip: the static schedule.
        kern = (_kernel(*geometry)[0] if T_pad == tile
                else _narrowed(seg.q, *geometry))
        return jax.vmap(kern, in_axes=(0, 0, 0, None))(q, k, v, seg)

    out = jax.vmap(row)(qt, kt, vt, seg)  # [B, Hkv, G, T_pad, D+]
    out = out[:, :, :, :T, :D].transpose(0, 3, 1, 2, 4).reshape(B, T, Hq, D)
    # A padding query's row is zero: inside a block of real tokens it
    # attended the padding before it, and a block of nothing but padding
    # ran no key block, so its row is 0 * (1 / 0).
    return jnp.where((q_segment_ids > 0)[:, :, None, None], out, 0)


def kernel_on_mesh(kernel, mesh, q, k, v, q_segment_ids, kv_segment_ids):
    """``kernel(q, k, v, q_segment_ids, kv_segment_ids)`` — a partial of
    :func:`window_attention` — under a multi-device mesh. GSPMD cannot
    partition a Mosaic kernel ("wrap the call in a shard_map"), so the call
    runs in a shard_map manual over every mesh axis not already manual (the
    pipeline stages are manual over "pp"): batch rows split over the data
    axes and heads over "tp" where the dims divide — an axis that does not
    divide, and pp/sp, compute redundantly. Attention has no cross-row or
    cross-head term, so the body needs no collective."""
    from jax.sharding import PartitionSpec as P

    from areal_tpu.parallel.mesh import DATA_AXES

    outer = jax.sharding.get_abstract_mesh()
    if outer.manual_axes:  # nested: shard_map wants the context's own mesh
        mesh = outer
    free = frozenset(mesh.axis_names) - frozenset(outer.manual_axes)
    data = tuple(a for a in DATA_AXES if a in free)
    n_data = 1
    for a in data:
        n_data *= mesh.shape[a]
    if q.shape[0] % n_data != 0:
        data = ()
    heads = ("tp" if "tp" in free and k.shape[2] % mesh.shape["tp"] == 0
             else None)
    qkv = P(data or None, None, heads, None)
    seg = P(data or None, None)
    return jax.shard_map(
        kernel,
        mesh=mesh,
        in_specs=(qkv, qkv, qkv, seg, seg),
        out_specs=qkv,
        axis_names=free,
        check_vma=False,
    )(q, k, v, q_segment_ids, kv_segment_ids)
