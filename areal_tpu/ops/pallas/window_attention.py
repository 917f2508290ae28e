"""TPU grouped-head attention for packed segment batches: causal
self-attention over one packed row, full or under a sliding window.

A query at position p of its document sees the keys of the same document
up to p — all of them, or under a ``window`` those at p-window+1 .. p
(``ops/attention.segment_mask``'s rule; packing keeps a document
contiguous in its row, so the distance inside the document is the
distance in the row). The hot op is jax's splash-attention kernel
(``jax.experimental.pallas.ops.tpu.splash_attention``) in its MQA form,
under a ``CausalMask`` or a ``LocalMask`` — the grid of a windowed call
holds only the key blocks the window touches: blocks wholly outside it
are never visited, forward or backward. Wrapped with areal_tpu's
packed-batch semantics:

 - inputs are [B, T, H, D]; self-attention only (queries and keys of one
   packed row);
 - GQA runs the kernel's MQA form once a key/value head (vmapped over
   rows and key/value heads): K and V are NOT repeated, and dK / dV come
   back at their own head count;
 - document masking via the kernel's segment ids, 0 = padding;
 - head_dim is padded up to the lane width (128) when needed, and the row
   up to a multiple of the tile of :func:`pick_tile`, with segment id 0.

The kernels' device ops are named ``splash_mqa_{fwd,dkv,dq}_segmented_*``
(a full-causal call runs no ``dq``: its backward is the one fused ``dkv``
kernel) — not ``flash_attention`` / ``flash_mha_bwd_*``, so a reader of
the flash kernels' time does not count them. Per compiled step,
:func:`geometry_counts` says which (length, padded length, tile, window)
each WINDOWED call was traced with, and how many key blocks it visits
against a causal kernel; :func:`causal_geometry_counts` which (length,
padded length, tile) each full-causal call was traced with.

CPU/testing: ``interpret=True`` runs the kernels in Pallas's plain
interpreter (tests/test_window_attention.py) — the TPU interpreter of
``pltpu.force_tpu_interpret_mode()`` does not take a vmapped grid;
tests/test_tpu_compile.py compiles the kernels for a described v5e.
"""

from __future__ import annotations

import collections
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as _splash,
)
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_mask as _mask,
)

from areal_tpu.ops import attention as _attention
from areal_tpu.ops.pallas.flash_attention import LANE, _round_up

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


def blocks_visited(n_pad: int, tile: int,
                   window: Optional[int]) -> Tuple[int, int]:
    """(key blocks a call over a padded row of ``n_pad`` tokens visits at
    ``tile``, key blocks a causal kernel would visit): query block i
    reaches back to key i*tile - window + 1, or with no window to key 0."""
    n = n_pad // tile
    causal = n * (n + 1) // 2
    if window is None:
        return causal, causal
    visited = sum(i - max(i * tile - window + 1, 0) // tile + 1
                  for i in range(n))
    return visited, causal


def pick_tile(n: int, window: Optional[int] = None) -> int:
    """The tile a row of n tokens runs: the one whose visited blocks at
    the padded length cost least, ``visited · t² · c(t)``; ties to the
    larger."""
    costs = CAUSAL_TILE_COST if window is None else TILE_COST

    def cost(t):
        visited, _ = blocks_visited(_round_up(n, t), t, window)
        return (visited * t * t * costs[t], -t)

    return min(costs, key=cost)


def padded_len(n: int, window: Optional[int] = None) -> Optional[int]:
    """The padded length the kernel runs a row of n tokens at; None when
    n is not a multiple of 128 (the caller takes the reference)."""
    if n % LANE:
        return None
    return _round_up(n, pick_tile(n, window))


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


def geometry_counts() -> Dict[str, Dict[Tuple[int, int, int, int], Dict]]:
    return {
        label: {g: dict(zip(("calls", "blocks_visited", "blocks_causal"), c))
                for g, c in geoms.items()}
        for label, geoms in _GEOMETRY.items()
    }


def causal_geometry_counts() -> Dict[str, Dict[Tuple[int, int, int], int]]:
    return {label: dict(c) for label, c in _CAUSAL_GEOMETRY.items()}


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


def _block_sizes(tile: int, window: Optional[int]) -> _splash.BlockSizes:
    """The kernels' blocks at ``tile``, as each table was measured: square
    and computed whole, dKV and dQ kernels, under a window; under a causal
    mask the key block computed ``_CAUSAL_KV_COMPUTE`` keys at a time and
    ONE fused backward kernel (5 matmuls a block pair where dKV + dQ do
    7; dQ leaves it a partial sum a key block, in the compute dtype, and
    is added up outside — under a window its grid would hold every causal
    block, so the windowed call keeps the two kernels)."""
    if window is not None:
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


def _kernel(n_pad: int, tile: int, window: Optional[int], group: int,
            interpret: bool = False):
    """The splash MQA kernel of one key/value head: ``group`` query heads
    over a row of ``n_pad`` tokens, causality (and the window: a
    ``LocalMask`` reaching ``window - 1`` back and 0 ahead) in the mask."""
    shape = (n_pad, n_pad)
    mask = (_mask.CausalMask(shape) if window is None
            else _mask.LocalMask(shape, (window - 1, 0), 0))
    return _splash.make_splash_mqa_single_device(
        _mask.MultiHeadMask([mask] * group),
        block_sizes=_block_sizes(tile, window),
        residual_checkpoint_name=RESIDUALS, interpret=interpret,
    )


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
    tile = pick_tile(T, window)
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

    kernel = _kernel(T_pad, tile, window, G, interpret)
    per_head = jax.vmap(kernel, in_axes=(0, 0, 0, None))  # key/value heads
    out = jax.vmap(per_head)(qt, kt, vt, seg)  # [B, Hkv, G, T_pad, D+]
    out = out[:, :, :, :T, :D].transpose(0, 3, 1, 2, 4).reshape(B, T, Hq, D)
    # Zero pad-query rows (they attended the row's other padding).
    return out * (q_segment_ids > 0)[:, :, None, None].astype(out.dtype)
