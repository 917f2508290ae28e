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
 - a head is padded up to whole lanes (128) when needed — 64 to 128, 192
   to 256 —, the VALUE by its own width where it is narrower than the key
   (latent attention's 128 under a key of 192: the kernel takes two
   widths, and no lane of a value padded to the key's is multiplied;
   :func:`head_width_counts` says what each call was handed and ran), and
   the row up to a multiple of the blocks of :func:`geometry`, with
   segment id 0.

Which blocks a call runs is :func:`geometry`'s choice from what the call
sees — the row, the window, the head size, the query heads a key/value
head — out of tables measured on the chip (tools/window_tile_sweep.py):
``TILE_COST`` under a window and ``CAUSAL_TILE_COST`` without at heads up
to 128 (square tiles, one cost a tile), ``WIDE_BLOCKS`` at wider heads (a
shape a kernel: forward, dKV, dQ).

The kernels' device ops are named ``splash_mqa_{fwd,dkv,dq}_segmented_*``
(a call whose backward is the ONE fused ``dkv`` kernel runs no ``dq``: a
full-causal call at heads up to 128). Per compiled step,
:func:`geometry_counts` says which (length, padded length, tile, window)
each WINDOWED call was traced with, and how many key blocks its static
mask visits against a causal kernel's; :func:`causal_geometry_counts`
which (length, padded length, :class:`Blocks`) each full-causal call was
traced with — the table entry it took; :func:`needed_counts` what the
packed grids the engine ran needed of those static blocks (counted on the
host, by :func:`count_needed`).

CPU/testing: ``interpret=True`` runs the kernels in Pallas's plain
interpreter (tests/test_window_attention.py) — the TPU interpreter of
``pltpu.force_tpu_interpret_mode()`` does not take a vmapped grid;
tests/test_tpu_compile.py compiles the kernels for a described v5e.
"""

from __future__ import annotations

import collections
import functools
import math
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
# :func:`geometry` — measured on a TPU v5e, 1 row x 6144 tokens in one
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
# Heads wider than the lane width under a window — unmeasured, no model
# has them — and wide heads WIDE_BLOCKS has no entry for run square tiles
# up to this, computed whole, dKV and dQ kernels: what compiled for every
# wide shape so far (PR 52).
_WIDE_FALLBACK_TILE = 512


def _round_up(n: int, tile: int) -> int:
    return -(-n // tile) * tile


def _static_blocks(n: int, tile: int, window: Optional[int],
                   block_kv: Optional[int] = None) -> np.ndarray:
    """bool [n, key blocks]: the blocks the static mask leaves — query
    block i reaches back to key i*tile - window + 1, or with no window to
    key 0. Key blocks of ``block_kv`` tokens where that is not the query
    block's ``tile`` (a causal mask only): every key block that starts at
    or before query block i's last token."""
    i = np.arange(n)[:, None]
    if block_kv not in (None, tile):
        assert window is None, "a windowed call runs square blocks"
        return np.arange(n * tile // block_kv) * block_kv < (i + 1) * tile
    j = i.T
    if window is None:
        return j <= i
    return (j <= i) & (j >= np.maximum(i * tile - window + 1, 0) // tile)


def blocks_visited(n_pad: int, tile: int, window: Optional[int],
                   block_kv: Optional[int] = None) -> Tuple[int, int]:
    """(key blocks the static mask of a call over a padded row of
    ``n_pad`` tokens visits at ``tile`` (key blocks of ``block_kv``
    tokens; default: of ``tile``), key blocks a causal mask would)."""
    n = n_pad // tile
    causal = _static_blocks(n, tile, None, block_kv)
    return int(_static_blocks(n, tile, window, block_kv).sum()), int(
        causal.sum())


def blocks_needed(segment_ids, tile: int, window: Optional[int] = None,
                  block_kv: Optional[int] = None):
    """bool [..., n, n] over the ``tile``-token blocks of a packed row
    (``segment_ids`` [..., n * tile], 0 = padding; numpy or traced): query
    block i needs key block j when j is in the static mask's range for i
    (:func:`blocks_visited`'s) and a token of i that is not padding
    belongs to a document with a token in j. A document is contiguous in
    its row, so the earliest document of block i — its first real token's
    — reaches back into block j < i exactly when block j ENDS in it: the
    needed blocks are one range up to i. A query block of nothing but
    padding needs no block. With key blocks of ``block_kv`` tokens
    ([..., n, key blocks]) the same rule: a key block that ends before
    query block i starts is needed when it ends in i's earliest document,
    and one that shares tokens with i when it holds a real one."""
    xp = jnp if isinstance(segment_ids, jax.Array) else np
    rows = segment_ids.reshape(*segment_ids.shape[:-1], -1, tile)
    real = rows > 0
    first = xp.take_along_axis(  # [..., n, 1]
        rows, xp.argmax(real, axis=-1)[..., None], axis=-1)
    n = rows.shape[-2]
    if block_kv not in (None, tile):  # (square blocks keep their own graph)
        keys = segment_ids.reshape(*segment_ids.shape[:-1], -1, block_kv)
        i, j = np.indices((n, keys.shape[-2]))
        own = (j * block_kv < (i + 1) * tile) & ((j + 1) * block_kv > i * tile)
        return (_static_blocks(n, tile, window, block_kv)
                & real.any(axis=-1)[..., None]
                & ((own & (keys > 0).any(axis=-1)[..., None, :])
                   | (keys[..., None, :, -1] == first)))
    last = rows[..., None, :, -1]  # [..., 1, n]
    return (_static_blocks(n, tile, window) & real.any(axis=-1)[..., None]
            & (np.eye(n, dtype=bool) | (last == first)))


def pick_tile(n: int, window: Optional[int] = None,
              max_tile: Optional[int] = None) -> int:
    """The tile a row of n tokens runs: the one whose visited blocks at
    the padded length cost least, ``visited · t² · c(t)``; ties to the
    larger; of the tiles up to ``max_tile`` where one is given."""
    costs = CAUSAL_TILE_COST if window is None else TILE_COST
    if max_tile is not None:
        costs = {t: c for t, c in costs.items() if t <= max_tile}

    def cost(t):
        visited, _ = blocks_visited(_round_up(n, t), t, window)
        return (visited * t * t * costs[t], -t)

    return min(costs, key=cost)


class Blocks(NamedTuple):
    """The geometry one call runs. Each kernel's (query block, key block
    fetched, key block computed at a time) — ``dq`` has no third, and
    ``dq`` None is the ONE fused backward kernel: 5 matmuls a block pair
    where dKV + dQ do 7; dQ leaves it a partial sum a key block, in the
    compute dtype, [key blocks, heads, row, head_dim], and is added up
    outside. These fix the GRID; which of its steps run is the static
    mask's schedule narrowed by the row's segment ids (:func:`_narrowed`):
    a skipped step costs a grid step and no DMA, and the fused backward
    still writes that step's dQ partial (zeros)."""
    fwd: Tuple[int, int, int]
    dkv: Tuple[int, int, int]
    dq: Optional[Tuple[int, int]]

    @property
    def tile(self) -> int:
        """What the row is padded to a multiple of: every block's."""
        return math.lcm(*self.fwd[:2], *self.dkv[:2], *(self.dq or ()))

    def sizes(self) -> _splash.BlockSizes:
        # A backward query block lies inside ONE forward query block: a
        # forward block of nothing but padding ran no key block and left
        # its softmax statistic -inf, which only a backward block of
        # nothing but padding — it runs no block either — may hold.
        assert all(self.fwd[0] % kernel[0] == 0
                   for kernel in (self.dkv, self.dq) if kernel), self
        dq = {} if self.dq is None else dict(block_q_dq=self.dq[0],
                                             block_kv_dq=self.dq[1])
        return _splash.BlockSizes(
            block_q=self.fwd[0], block_kv=self.fwd[1],
            block_kv_compute=self.fwd[2],
            block_q_dkv=self.dkv[0], block_kv_dkv=self.dkv[1],
            block_kv_dkv_compute=self.dkv[2],
            use_fused_bwd_kernel=self.dq is None, **dq)

    def label(self) -> str:
        """``f<QxKVxC>.kv<QxKVxC>.q<QxKV>|fused``."""
        x = "x".join
        return ".".join((
            "f" + x(map(str, self.fwd)), "kv" + x(map(str, self.dkv)),
            "fused" if self.dq is None else "q" + x(map(str, self.dq))))


def _square(tile: int, compute: Optional[int] = None,
            fused: bool = False) -> Blocks:
    kernel = (tile, tile, compute or tile)
    return Blocks(kernel, kernel, None if fused else (tile, tile))


# Heads wider than the lanes under a causal mask, {(head_dim, query heads
# a key/value head): blocks} — each kernel's own best shape of those the
# chip's 16 MB of scoped VMEM takes, measured on a TPU v5e by each
# kernel's DEVICE time, bf16, at the rows and document layouts of the
# cells that have such heads: glm-4.7-flash's latent attention (20 / 20
# heads; 14,336 = 8,937 + 5,357, 13,440 = 13,356 and = 6,525 x 2) and
# qwen3-next's gated attention (16 / 2; 14,336 = 11,737 + 2,488, 8,704 =
# 5,176 + 3,519): tools/window_tile_sweep.py --window 0 --head-dim 256
# --device; PERF.md §5, PR 61. Blocks of 1024 whose keys are computed 256
# at a time take the forward 16-25 % under the square 512 computed whole
# that PR 52 shipped because it compiled (the [block_q, compute] float32
# temporaries were what overflowed), the dKV kernel 3-15 % and the dQ
# kernel 4-14 %; with eight grouped heads the backward's query block is
# better at 512. The dKV + dQ pair, not the fused backward: its dQ partial
# sums — [key blocks, heads, row, head_dim], every head of the call at
# once — are 2.06 GB at 20 heads over 14,336 tokens and 1.64 GB at 2 x 8
# (key blocks of 1024), and the tighter cell has ~1.0 GB of room (PERF.md
# §6, PR 61: it was 10 % under the pair where memory is no object).
WIDE_BLOCKS = {
    (256, 1): Blocks((1024, 1024, 256), (1024, 1024, 512), (1024, 1024)),
    (256, 8): Blocks((1024, 1024, 256), (512, 1024, 256), (512, 1024)),
    # A key of 192 in 256 lanes over a value of 128 in its own
    # (kimi_linear's latent attention, 32 / 32 heads; 8,192 = 1,658 + 6,480
    # and 7,552 = 5,062 + 1,682; tools/window_tile_sweep.py --window 0
    # --head-dim 192 --value-dim 128 --device; PERF.md §6, PR 63): the
    # (256, 1) entry's shapes are the best here too — 41.6 / 32.3 ms for two
    # forwards and a forward + backward against the square 512's 45.4 /
    # 36.6; a forward key block of 2048 is 7 % slower, a dKV / dQ key block
    # of 2048 overflows VMEM at 7,552, a backward query block of 512 is
    # 2-3 % slower.
    (192, 1): Blocks((1024, 1024, 256), (1024, 1024, 512), (1024, 1024)),
}


def _wide_blocks(n: int, head_dim: int, group: int) -> Blocks:
    """The entry measured at exactly this head size and group; the
    fallback's square pair where there is none — add an entry when such a
    model arrives and is swept — or the row is under two of the entry's
    blocks (it was measured at rows of 8.7k-14k tokens, and would pad a
    short row to 1024)."""
    blocks = WIDE_BLOCKS.get((head_dim, group))
    if blocks is None or n < 2 * blocks.tile:
        return _square(pick_tile(n, None, _WIDE_FALLBACK_TILE))
    return blocks


def geometry(n: int, window: Optional[int] = None, head_dim: int = LANE,
             group: int = 1) -> Blocks:
    """The blocks a call over rows of n tokens runs, as each table was
    measured. Heads up to the lane width: square tiles of
    :func:`pick_tile`; under a window computed whole by dKV and dQ kernels
    (the fused kernel's grid would hold every causal block), under a
    causal mask the key block computed ``_CAUSAL_KV_COMPUTE`` keys at a
    time and the fused backward. Wider heads under a causal mask: the
    measured entry of :func:`_wide_blocks` for ``head_dim`` and the
    ``group`` of query heads a key/value head; under a window the
    fallback's square tiles."""
    if head_dim > LANE and window is None:
        return _wide_blocks(n, head_dim, group)
    tile = pick_tile(n, window, _WIDE_FALLBACK_TILE if head_dim > LANE
                     else None)
    if window is not None:
        return _square(tile)
    compute = tile if tile % _CAUSAL_KV_COMPUTE else _CAUSAL_KV_COMPUTE
    return _square(tile, compute, fused=True)


def padded_len(n: int, window: Optional[int] = None, head_dim: int = LANE,
               group: int = 1) -> Optional[int]:
    """The padded length the kernel runs a row of n tokens at; None when
    n is not a multiple of 128 (the caller takes the reference)."""
    if n % LANE:
        return None
    return _round_up(n, geometry(n, window, head_dim, group).tile)


# Which (length, padded length, tile, window) each WINDOWED call TRACED
# with, by the label of the compiled step (ops/attention.dispatch_label),
# and the key blocks it visits / a causal kernel would:
# {label: {(n, n_pad, tile, window): [calls, visited, causal]}}.
_GEOMETRY: Dict[str, Dict[Tuple[int, int, int, int], list]] = (
    collections.defaultdict(dict))
# Which (length, padded length, blocks) each FULL-CAUSAL call traced with
# — the :class:`Blocks` that ran: each kernel's blocks, the fused backward
# or the pair; a count of its own: readers of the windowed one take its
# calls for a sliding layer's.
# {label: {(n, n_pad, blocks): calls}}.
_CAUSAL_GEOMETRY: Dict[str, collections.Counter] = collections.defaultdict(
    collections.Counter)
# The head widths each full-causal geometry was handed and ran: {label:
# {(n, n_pad, blocks): (key width, its lanes, value width, its lanes)}}.
_HEAD_WIDTHS: Dict[str, Dict[Tuple, Tuple[int, int, int, int]]] = (
    collections.defaultdict(dict))


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


def causal_geometry_counts() -> Dict[str, Dict[Tuple[int, int, Blocks], int]]:
    return {label: dict(c) for label, c in _CAUSAL_GEOMETRY.items()}


def head_width_counts() -> Dict[str, Dict[Tuple, Tuple[int, int, int, int]]]:
    """{label: {(n, n_pad, blocks): (the key's width as handed, the lanes
    the kernel ran it in, the value's width, its lanes)}} of the
    full-causal calls: a value narrower than its key keeps its own lanes."""
    return {label: dict(w) for label, w in _HEAD_WIDTHS.items()}


def count_needed(segment_ids: np.ndarray,  # [R, L] on the host
                 window: Optional[int] = None,
                 head_dim: int = LANE, group: int = 1) -> Tuple[int, int]:
    """(key blocks the rows of a packed grid need, key blocks the static
    mask visits) at the forward kernel's blocks and the padded length
    :func:`window_attention` runs them at — the same :func:`blocks_needed`
    the kernel's schedule is narrowed by; a row of ONE block keeps the
    static schedule. Added to :func:`needed_counts` under the grid's
    shape."""
    R, L = segment_ids.shape
    blocks = geometry(L, window, head_dim, group)
    tile, (block_q, block_kv, _) = blocks.tile, blocks.fwd
    n_pad = _round_up(L, tile)
    visited = needed = R * blocks_visited(n_pad, block_q, window, block_kv)[0]
    if n_pad > tile:
        needed = int(blocks_needed(
            np.pad(segment_ids, [(0, 0), (0, n_pad - L)]), block_q,
            window, block_kv).sum())
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


def _count(n: int, n_pad: int, blocks: Blocks,
           window: Optional[int], widths: Tuple[int, int, int, int]) -> None:
    label = _attention.active_label()
    if window is None:
        _CAUSAL_GEOMETRY[label][(n, n_pad, blocks)] += 1
        _HEAD_WIDTHS[label][(n, n_pad, blocks)] = widths
        return
    tile = blocks.tile
    visited, causal = blocks_visited(n_pad, tile, window)
    c = _GEOMETRY[label].setdefault((n, n_pad, tile, window), [0, 0, 0])
    c[0] += 1
    c[1] += visited
    c[2] += causal


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


def _walk(info, by_key: bool, n_pad: int, block_q: int,
          block_kv: int) -> Optional[_Walk]:
    """An entry (r, c) of a forward / dQ mask info is query block r
    against the key block its ``data_next`` names; of a dKV info
    (``by_key``) the query block its ``data_next`` names against key
    block c (the other side of either may be shrunk to the blocks the
    static mask visits). The kernel's blocks need not be square: a pair
    indexes ``blocks_needed(ids, block_q, window, block_kv)``."""
    if info is None:
        return None
    block, data = (np.asarray(x)[0].astype(np.int32)
                   for x in (info.block_mask, info.data_next))
    r, c = np.indices(block.shape)
    qi, ki = (data, c) if by_key else (r, data)
    nq, nk = n_pad // block_q, n_pad // block_kv
    base = nq if by_key else nk  # above every ``data_next`` entry
    order = (lambda x: x.T.reshape(-1)) if by_key else (
        lambda x: x.reshape(-1))
    live = order(block > 0)
    return _Walk(live, order(block), np.where(live, order(qi * nk + ki), 0),
                 np.arange(block.size) * base + order(data), base, by_key)


def _infos(kernel):
    return kernel.fwd_mask_info, kernel.dq_mask_info, kernel.dkv_mask_info


def _shapes(sizes: _splash.BlockSizes):
    """(query block, key block) of the forward, the dQ and the dKV
    kernel."""
    return ((sizes.block_q, sizes.block_kv),
            (sizes.block_q_dq, sizes.block_kv_dq),
            (sizes.block_q_dkv, sizes.block_kv_dkv))


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
    return kernel, tuple(
        _walk(info, by_key, n_pad, *shape) for info, by_key, shape in zip(
            _infos(kernel), (False, False, True), _shapes(sizes)))


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
    needed = {}  # one matrix a block shape: square blocks share it

    def narrow(walk, info, shape):
        if shape not in needed:
            needed[shape] = blocks_needed(segment_ids, shape[0], window,
                                          shape[1])
        return _narrow(walk, needed[shape], info)

    return tuple(None if walk is None else narrow(walk, info, shape)
                 for walk, info, shape in zip(walks, _infos(kernel),
                                              _shapes(sizes)))


def _narrowed(segment_ids: jnp.ndarray, *geometry):
    """The geometry's kernel (:func:`_kernel`'s arguments) with the
    schedules of its forward and backward kernels narrowed to the blocks
    the row needs: the mask function and the segment ids still mask
    INSIDE a block, as under the static schedule."""
    kernel, _ = _kernel(*geometry)
    return _splash.SplashAttentionKernel(
        *(info and info._replace(block_mask=now[0], data_next=now[1])
          for info, now in zip(_infos(kernel),
                               _schedules(segment_ids, *geometry))),
        **kernel.kwargs)


@functools.partial(jax.named_call, name="pallas_window_attention")
def window_attention(
    q: jnp.ndarray,  # [B, T, Hq, D]
    k: jnp.ndarray,  # [B, T, Hkv, D]
    v: jnp.ndarray,  # [B, T, Hkv, Dv], Dv <= D
    q_segment_ids: jnp.ndarray,  # [B, T] int, 0 = pad
    kv_segment_ids: jnp.ndarray,  # [B, T]
    window: Optional[int] = None,  # None: full causal
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    B, T, Hq, D = q.shape
    Hkv, Dv = k.shape[2], v.shape[3]
    if k.shape[1] != T:
        raise ValueError("window_attention is self-attention over one "
                         f"packed row: T={T}, S={k.shape[1]}")
    if T % LANE:
        raise ValueError(
            f"row length T={T} is no multiple of 128; "
            "ops/attention.packed_attention routes such shapes to the "
            "reference")
    G = Hq // Hkv
    blocks = geometry(T, window, D, G)
    tile = blocks.tile
    T_pad = _round_up(T, tile)
    _count(T, T_pad, blocks, window,
           (D, _round_up(D, LANE), Dv, _round_up(Dv, LANE)))
    if scale is None:
        scale = D ** -0.5

    # [B, T, H, D] -> [B, Hkv, G, T, D] / [B, Hkv, T, D], heads padded to
    # whole lanes — the value by its own width — and the row to its tile;
    # the kernel takes no scale.
    more = T_pad - T

    def pad(x):  # [..., L, D]
        lanes = -x.shape[-1] % LANE
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

    kernel = (T_pad, window, G, blocks.sizes(), interpret)

    def row(q, k, v, seg):  # one packed row, a key/value head at a time
        # A row of ONE block has nothing to skip: the static schedule.
        kern = (_kernel(*kernel)[0] if T_pad == tile
                else _narrowed(seg.q, *kernel))
        return jax.vmap(kern, in_axes=(0, 0, 0, None))(q, k, v, seg)

    out = jax.vmap(row)(qt, kt, vt, seg)  # [B, Hkv, G, T_pad, D+]
    out = out[:, :, :, :T, :Dv].transpose(0, 3, 1, 2, 4).reshape(
        B, T, Hq, Dv)
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
