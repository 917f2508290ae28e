"""TPU sliding-window attention for packed segment batches.

The windowed twin of ``flash_attention.py``: a query at position p of its
document sees the keys at p-window+1 .. p of the same document
(``ops/attention.segment_mask``'s rule; packing keeps a document
contiguous in its row, so the distance inside the document is the
distance in the row). jax's flash kernel has no window, and a mask alone
over it would still visit every causal key block; the hot op here is
jax's splash-attention kernel
(``jax.experimental.pallas.ops.tpu.splash_attention``) under a
``LocalMask``, whose grid holds only the key blocks the window touches —
blocks wholly outside it are never visited, forward or backward. Wrapped
with areal_tpu's packed-batch semantics:

 - inputs are [B, T, H, D]; self-attention only (queries and keys of one
   packed row);
 - GQA runs the kernel's MQA form once a key/value head (vmapped over
   rows and key/value heads): K and V are NOT repeated;
 - document masking via the kernel's segment ids, 0 = padding;
 - head_dim is padded up to the lane width (128) when needed, and the row
   up to a multiple of the tile of :func:`pick_tile`, with segment id 0.

The kernels' device ops are named ``splash_mqa_{fwd,dkv,dq}_segmented_*``
— not ``flash_attention`` / ``flash_mha_bwd_*``, so a reader of the flash
kernels' time does not count them. :func:`geometry_counts` says, per
compiled step, which (length, padded length, tile, window) each call was
traced with, and how many key blocks it visits against a causal kernel.

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

# The device scope around the kernel, inside the transformer's "attention"
# (base/telemetry.WINDOW_SCOPES).
SCOPE = "window_attention"
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


def blocks_visited(n_pad: int, tile: int, window: int) -> Tuple[int, int]:
    """(key blocks a call over a padded row of ``n_pad`` tokens visits at
    ``tile``, key blocks a causal kernel would visit): query block i
    reaches back to key i*tile - window + 1."""
    n = n_pad // tile
    visited = sum(i - max(i * tile - window + 1, 0) // tile + 1
                  for i in range(n))
    return visited, n * (n + 1) // 2


def pick_tile(n: int, window: int) -> int:
    """The tile a row of n tokens runs: the one whose visited blocks at
    the padded length cost least, ``visited · t² · c(t)``; ties to the
    larger."""
    def cost(t):
        visited, _ = blocks_visited(_round_up(n, t), t, window)
        return (visited * t * t * TILE_COST[t], -t)

    return min(TILE_COST, key=cost)


def padded_len(n: int, window: int) -> Optional[int]:
    """The padded length the kernel runs a row of n tokens at; None when
    n is not a multiple of 128 (the caller takes the reference)."""
    if n % LANE:
        return None
    return _round_up(n, pick_tile(n, window))


# Which (length, padded length, tile, window) each call TRACED with, by the
# label of the compiled step (ops/attention.dispatch_label), and the key
# blocks it visits / a causal kernel would:
# {label: {(n, n_pad, tile, window): [calls, visited, causal]}}.
_GEOMETRY: Dict[str, Dict[Tuple[int, int, int, int], list]] = (
    collections.defaultdict(dict))


def geometry_counts() -> Dict[str, Dict[Tuple[int, int, int, int], Dict]]:
    return {
        label: {g: dict(zip(("calls", "blocks_visited", "blocks_causal"), c))
                for g, c in geoms.items()}
        for label, geoms in _GEOMETRY.items()
    }


def _count(n: int, n_pad: int, tile: int, window: int) -> None:
    visited, causal = blocks_visited(n_pad, tile, window)
    c = _GEOMETRY[_attention.active_label()].setdefault(
        (n, n_pad, tile, window), [0, 0, 0])
    c[0] += 1
    c[1] += visited
    c[2] += causal


def _kernel(n_pad: int, tile: int, window: int, group: int,
            interpret: bool = False):
    """The splash MQA kernel of one key/value head: ``group`` query heads
    over a row of ``n_pad`` tokens, window and causality in the mask (a
    ``LocalMask`` reaching ``window - 1`` back and 0 ahead)."""
    local = _mask.LocalMask((n_pad, n_pad), (window - 1, 0), 0)
    sizes = _splash.BlockSizes(
        block_q=tile, block_kv=tile, block_kv_compute=tile,
        block_q_dkv=tile, block_kv_dkv=tile, block_kv_dkv_compute=tile,
        block_q_dq=tile, block_kv_dq=tile,
    )
    return _splash.make_splash_mqa_single_device(
        _mask.MultiHeadMask([local] * group), block_sizes=sizes,
        residual_checkpoint_name=RESIDUALS, interpret=interpret,
    )


@functools.partial(jax.named_call, name="pallas_window_attention")
def window_attention(
    q: jnp.ndarray,  # [B, T, Hq, D]
    k: jnp.ndarray,  # [B, T, Hkv, D]
    v: jnp.ndarray,  # [B, T, Hkv, D]
    q_segment_ids: jnp.ndarray,  # [B, T] int, 0 = pad
    kv_segment_ids: jnp.ndarray,  # [B, T]
    window: int = 0,
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
