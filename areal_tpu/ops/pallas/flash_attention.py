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

Block-size selection (the device-efficiency lever named in
docs/benchmarks.md "Where the time goes"): ``pick_block_sizes`` resolves
(block_q, block_kv) for a (T, S) geometry from, in precedence order,

 1. ``AREAL_FLASH_BLOCKS="bq,bkv"`` — a global pin (debug/experiments);
 2. a geometry-keyed table: entries recorded at runtime via
    :func:`set_block_sizes`, or loaded from the JSON file named by
    ``AREAL_FLASH_BLOCK_TABLE`` (written by ``perf_probe blocksweep``,
    format ``{"T,S": [bq, bkv]}``);
 3. :func:`pick_tile` — the tile of ``TILE_COST`` that makes the row
    cheapest once the row is PADDED up to a multiple of it.

A pin or table entry means "these blocks, no padding": it is validated
against the kernel's divisibility constraint and snaps DOWN to the nearest
dividing 128-multiple rather than failing at dispatch time. The tile of
rule 3 need not divide the row: :func:`flash_attention` pads T and S up to
it with segment id 0 (what a row's own tail padding already carries), runs
the kernel at the padded length and slices the output back, so a row of
6016 = 47 x 128 tokens runs 512-blocks at 6144 instead of 128-blocks.
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
import json
import logging
import os
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
DEFAULT_BLOCK_TARGET = 512

# c(t): ns per row·token² that the kernels of one training step (3 forward
# passes, dKV, dQ) take at tile t — measured on a TPU v5e, the kernels
# alone, 6 rows x 6144 tokens, 14 heads, bf16 (PERF.md §5, PR 25).
# pick_tile uses only their ratios.
TILE_COST = {512: 0.2724, 384: 0.4054, 256: 0.5587, 128: 1.5418}

logger = logging.getLogger("areal_tpu")

# Geometry-keyed (T, S) -> (block_q, block_kv). Populated by
# set_block_sizes() / the AREAL_FLASH_BLOCK_TABLE JSON (perf_probe
# blocksweep writes it); empty by default — pick_tile below is the
# default, and recorded sweep results override it per geometry.
_BLOCK_TABLE: Dict[Tuple[int, int], Tuple[int, int]] = {}
_TABLE_FILE_LOADED: Optional[str] = None  # set only on a SUCCESSFUL load
_TABLE_FILE_WARNED: set = set()


def _block(n: int, target: int) -> Optional[int]:
    """Largest multiple of 128 that divides n and is ≤ target (the kernel
    requires block sizes to divide the sequence dims exactly). None when no
    such divisor exists — callers fall back to the reference path."""
    for b in range(min(target, n), 0, -LANE):
        if n % b == 0 and b % LANE == 0:
            return b
    return None


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


def set_block_sizes(T: int, S: int, block_q: int, block_kv: int) -> None:
    """Record tuned block sizes for a (T, S) geometry (process-local)."""
    _BLOCK_TABLE[(int(T), int(S))] = (int(block_q), int(block_kv))


def clear_block_table() -> None:
    """Drop runtime + file-loaded entries (tests / re-sweeps)."""
    global _TABLE_FILE_LOADED
    _BLOCK_TABLE.clear()
    _TABLE_FILE_LOADED = None


def _load_table_file() -> None:
    """Merge ``AREAL_FLASH_BLOCK_TABLE`` (if set) into the table once per
    path; runtime set_block_sizes entries win over file entries. A missing
    or unreadable file warns once but is retried on later calls (the
    documented workflow writes the file with ``perf_probe blocksweep``
    AFTER the env var is already exported), and only a successful load
    pins the path as done."""
    global _TABLE_FILE_LOADED
    path = os.environ.get("AREAL_FLASH_BLOCK_TABLE")
    if not path or path == _TABLE_FILE_LOADED:
        return
    try:
        with open(path) as f:
            raw = json.load(f)
        for key, val in raw.items():
            t, s = (int(x) for x in key.split(","))
            _BLOCK_TABLE.setdefault((t, s), (int(val[0]), int(val[1])))
        _TABLE_FILE_LOADED = path
        _TABLE_FILE_WARNED.discard(path)
    except (OSError, ValueError, KeyError, IndexError) as e:
        if path not in _TABLE_FILE_WARNED:
            _TABLE_FILE_WARNED.add(path)
            logger.warning("AREAL_FLASH_BLOCK_TABLE %r unreadable (%s); "
                           "using pick_tile's block sizes until it appears",
                           path, e)


def pick_block_sizes(T: int, S: int) -> Optional[Tuple[int, int]]:
    """Resolve (block_q, block_kv) for a geometry; None when either dim is
    not a multiple of 128 (caller must use the reference path). Env pin >
    table (runtime or file) > :func:`pick_tile`. A pin or a table entry is
    snapped down to the nearest dividing 128-multiple (no padding); the
    tile of pick_tile may exceed a divisor — flash_attention pads the dim
    up to it."""
    if _block(T, T) is None or _block(S, S) is None:
        return None
    # Any 128-multiple divisor of n implies 128 | n, so once the checks
    # above pass the largest divisor <= 512 can never miss — it is the safe
    # landing spot for out-of-range pins/table entries (a sub-128 pin must
    # NOT snap up to a whole-sequence tile: bq*bkv scores alone would blow
    # VMEM).
    heur_q = _block(T, DEFAULT_BLOCK_TARGET)
    heur_kv = _block(S, DEFAULT_BLOCK_TARGET)
    env = os.environ.get("AREAL_FLASH_BLOCKS")
    if env:
        try:
            bq, bkv = (int(x) for x in env.split(","))
            return (_block(T, min(bq, T)) or heur_q,
                    _block(S, min(bkv, S)) or heur_kv)
        except ValueError:
            logger.warning("AREAL_FLASH_BLOCKS=%r not 'bq,bkv'; ignoring",
                           env)
    _load_table_file()
    hit = _BLOCK_TABLE.get((T, S))
    if hit is not None:
        return (_block(T, min(hit[0], T)) or heur_q,
                _block(S, min(hit[1], S)) or heur_kv)
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
    # Pad heads up to the lane width and each sequence dim up to its tile
    # (a pin or table entry divides its dim: nothing to pad), in one pass.
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
    """:func:`flash_attention` under a multi-device mesh. GSPMD cannot
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
        functools.partial(flash_attention, causal=causal, scale=scale),
        mesh=mesh,
        in_specs=(qkv, qkv, qkv, seg, seg),
        out_specs=qkv,
        axis_names=free,
        check_vma=False,
    )(q, k, v, q_segment_ids, kv_segment_ids)

