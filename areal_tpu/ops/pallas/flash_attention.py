"""TPU flash attention for packed segment batches.

Role parity: the reference's flash-attn varlen path
(``realhf/impl/model/modules/attn.py:24-27``). The hot op is delegated to
JAX's Pallas TPU flash-attention kernel
(``jax.experimental.pallas.ops.tpu.flash_attention``) — block-streamed
online-softmax with fused forward/backward kernels — wrapped here with
areal_tpu's packed-batch semantics:

 - inputs are [B, T, H, D] (time-major heads-minor, the model layout);
 - GQA: kv heads are expanded to the q head count before the kernel (the
   kernel wants matching head counts; the expansion is O(B·S·Hq·D) HBM but
   keeps the inner loop dense on the MXU);
 - document masking via SegmentIds — block-causal by grid column, which
   equals per-document causal order because packing keeps documents
   contiguous within a row (models/packing.py);
 - head_dim is padded up to the lane width (128) when needed.

Block-size selection has one rule and one padding regime:
``pick_block_sizes`` gives each sequence dim of a (T, S) geometry the tile
of :func:`pick_tile` — the tile of ``TILE_COST`` that makes the row
cheapest once the row is PADDED up to a multiple of it. The tile need not
divide the row: :func:`flash_attention` pads T and S up to it with segment
id 0 (what a row's own tail padding already carries), runs the kernel at
the padded length and slices the output back, so a row of 6016 = 47 x 128
tokens runs 512-blocks at 6144 instead of 128-blocks.
:func:`geometry_counts` says, per compiled step, which (length, padded
length, tile) each call was traced with.

Sequence dims that are NOT a multiple of 128 are not tiled:
:func:`flash_attention` raises for them, and the dispatcher
(ops/attention.packed_attention) asks :func:`pick_block_sizes` first and
runs — and counts — the XLA reference instead. Packed training rows never
land there (the packer's 128-token length bucket); bucketed prompts can.

CPU/testing: wrap calls in ``pltpu.force_tpu_interpret_mode()`` — the
parity tests (tests/test_pallas_attention.py) run the same kernel
interpreted; tests/test_tpu_compile.py compiles it for a described v5e.
"""

from __future__ import annotations

import collections
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.flash_attention import (
    BlockSizes,
    SegmentIds,
)
from jax.experimental.pallas.ops.tpu.flash_attention import (
    flash_attention as _jax_flash,
)

from areal_tpu.ops import attention as _attention

LANE = 128

# c(t): ns per row·token² that the kernels of one training step (3 forward
# passes, dKV, dQ) take at tile t — measured on a TPU v5e, the kernels
# alone, 6 rows x 6144 tokens, 14 heads, bf16 (PERF.md §5, PR 25).
# pick_tile uses only their ratios.
TILE_COST = {512: 0.2724, 384: 0.4054, 256: 0.5587, 128: 1.5418}


def _round_up(n: int, tile: int) -> int:
    return -(-n // tile) * tile


def pick_tile(n: int) -> int:
    """The tile a sequence dim of n tokens runs: the one whose padded
    length costs least, ``round_up(n, t)² · c(t)``; ties to the larger."""
    return min(TILE_COST,
               key=lambda t: (_round_up(n, t) ** 2 * TILE_COST[t], -t))


# Which (length, padded length, tile) each flash_attention call TRACED
# with, per sequence dim, by the label of the compiled step (the label of
# ops/attention.dispatch_label): {label: {(n, n_pad, tile): calls}}.
_GEOMETRY: Dict[str, collections.Counter] = collections.defaultdict(
    collections.Counter
)


def geometry_counts() -> Dict[str, Dict[Tuple[int, int, int], int]]:
    return {label: dict(c) for label, c in _GEOMETRY.items()}


def pick_block_sizes(T: int, S: int) -> Optional[Tuple[int, int]]:
    """(block_q, block_kv) for a geometry: each dim's :func:`pick_tile`,
    which flash_attention pads the dim up to. None when either dim is not
    a multiple of 128 (the caller must use the reference path)."""
    if T % LANE or S % LANE:
        return None
    return (pick_tile(T), pick_tile(S))


@functools.partial(
    jax.named_call, name="pallas_flash_attention"
)
def flash_attention(
    q: jnp.ndarray,  # [B, T, Hq, D]
    k: jnp.ndarray,  # [B, S, Hkv, D]
    v: jnp.ndarray,  # [B, S, Hkv, D]
    q_segment_ids: jnp.ndarray,  # [B, T] int, 0 = pad
    kv_segment_ids: jnp.ndarray,  # [B, S]
    q_positions: Optional[jnp.ndarray] = None,  # accepted for API parity
    kv_positions: Optional[jnp.ndarray] = None,
    causal: bool = True,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    B, T, Hq, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    blocks = pick_block_sizes(T, S)
    if blocks is None:
        raise ValueError(
            f"sequence dims T={T} S={S} have no 128-multiple block; "
            "ops/attention.packed_attention routes such shapes to the "
            "reference"
        )
    bq, bkv = blocks
    T_pad, S_pad = _round_up(T, bq), _round_up(S, bkv)
    for geom in {(T, T_pad, bq), (S, S_pad, bkv)}:
        _GEOMETRY[_attention.active_label()][geom] += 1
    if scale is None:
        scale = D ** -0.5
    if Hq != Hkv:
        G = Hq // Hkv
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)

    # [B, T, H, D] → [B, H, T, D] kernel layout.
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    # Pad heads up to the lane width and each sequence dim up to its tile,
    # in one pass.
    lanes = max(LANE - D, 0)

    def pad(x, n):  # [B, H, L, D]: n more tokens, `lanes` more lanes
        if not (n or lanes):
            return x
        return jnp.pad(x, [(0, 0), (0, 0), (0, n), (0, lanes)])

    def pad_ids(ids, n):  # [B, L]
        return jnp.pad(ids, [(0, 0), (0, n)]) if n else ids

    qt = pad(qt, T_pad - T)
    kt, vt = pad(kt, S_pad - S), pad(vt, S_pad - S)

    # Padding rows (segment id 0) must not alias into a real segment; the
    # kernel's segment mask handles it as long as pad ids differ between a
    # q pad and kv real token — id 0 == id 0 would attend pad→pad only,
    # which is harmless (output rows for pad queries are discarded), but we
    # keep them NaN-free by masking afterwards instead. The tokens added
    # above carry id 0 like the row's own tail padding.
    seg = SegmentIds(q=pad_ids(q_segment_ids, T_pad - T),
                     kv=pad_ids(kv_segment_ids, S_pad - S))

    sizes = BlockSizes(
        block_q=bq, block_k_major=bkv, block_k=bkv, block_b=1,
        block_q_major_dkv=bq, block_k_major_dkv=bkv,
        block_k_dkv=bkv, block_q_dkv=bq,
        block_k_major_dq=bkv, block_k_dq=bkv, block_q_dq=bq,
    )
    out = _jax_flash(
        qt, kt, vt, segment_ids=seg, causal=causal, sm_scale=scale,
        block_sizes=sizes,
    )
    out = out[:, :, :T, :D].transpose(0, 2, 1, 3)
    # Zero pad-query rows (the kernel leaves them unspecified-but-finite).
    return out * (q_segment_ids > 0)[:, :, None, None].astype(out.dtype)


def flash_attention_on_mesh(
    mesh,
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_segment_ids: jnp.ndarray,
    kv_segment_ids: jnp.ndarray,
    causal: bool = True,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """:func:`flash_attention` under a multi-device mesh."""
    return kernel_on_mesh(
        functools.partial(flash_attention, causal=causal, scale=scale),
        mesh, q, k, v, q_segment_ids, kv_segment_ids)


def kernel_on_mesh(kernel, mesh, q, k, v, q_segment_ids, kv_segment_ids):
    """``kernel(q, k, v, q_segment_ids, kv_segment_ids)`` — this module's
    or window_attention's — under a multi-device mesh. GSPMD cannot
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

