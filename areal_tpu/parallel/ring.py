"""Ring attention v2 — context parallelism over the mesh's "sp" axis.

Fills the reference's explicit long-context gap (SURVEY §5: "No ring
attention, no Ulysses, no context parallelism anywhere in the repo" — the
reference leans on Megatron-SP + flash-attn only). The v1 contiguous
schedule (every step computes the full local attention einsum) is kept as
the parity ORACLE (``schedule="naive"``); the default ``zigzag`` schedule
is the production path:

 - **zig-zag (striped) layout** — the global sequence splits into ``2n``
   chunks of ``c = T/(2n)``; ring rank ``r`` holds chunk ``r`` (early) and
   chunk ``2n-1-r`` (late), so causal work balances across the ring
   (contiguous layout leaves rank 0 with one visible KV block and rank
   n-1 with all n). The layout is a pure index permutation applied to the
   global sequence dim at the shard boundary (and inverted on the way
   out), so callers see identical global semantics — packed
   multi-document ``segment_ids`` masking included;
 - **masked-block skip** — at ring step ``i > 0`` the visiting KV block's
   origin differs from the resident rank, and under the zig-zag layout
   exactly two of the four (q-half × kv-half) tiles are causally visible:
   ``q_late × kv_early`` always, plus ``q_early × kv_early`` when the
   block came from a lower rank or ``q_late × kv_late`` from a higher
   one. The *count* of executed tiles is a trace-time constant — the
   fully-masked tiles are never built — so per step only half the naive
   area runs and the total is ``(n+1)/2n`` of v1's FLOPs (the step-0
   diagonal still needs the full causal mask). Which tile runs is traced
   (``jnp.where`` on operands and accumulators), keeping shapes static;
 - **comm/compute overlap** — the ``lax.ppermute`` rotating KV+segments to
   the next rank is issued *before* the current block's compute
   (double-buffering), so XLA's latency-hiding scheduler can fly the
   transfer under the einsums; the final (useless) rotation is dropped
   (``n-1`` rotations vs v1's ``n``);
 - masking: block-causal by GLOBAL grid column + same-segment, padding
   (segment 0) always masked; fully differentiable (``ppermute`` has a
   transpose rule) — no custom VJP.

Two entry points: :func:`ring_attention` wraps its own full-manual
``shard_map`` (the GSPMD forward path), while :func:`ring_attention_inline`
runs the same local body for callers *already inside* a manual region over
the ring axis — the PP∘SP pipeline stages (parallel/pipeline.py), which
build a :class:`RingCtx` from their sharded-iota rank.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from areal_tpu.models.config import CONV, GDN, KDA, MAMBA, S6, SSD
from areal_tpu.parallel.mesh import DATA_AXES

_NEG_INF = -1e30

SCHEDULES = ("zigzag", "naive")
# What a call that names no schedule runs (the pipeline stages' inline
# ring among them).
DEFAULT_SCHEDULE = "zigzag"

# Trace-time structural counters: incremented while the schedule is being
# traced (plain Python), so tests can prove the masked-block skip without
# inspecting HLO — executed_area counts q×kv cells actually handed to
# _block_attention_online, naive_area what the v1 schedule would run.
_COUNTERS: Dict[str, int] = {
    "block_calls": 0, "executed_area": 0, "naive_area": 0,
}


def reset_ring_counters() -> None:
    for k in _COUNTERS:
        _COUNTERS[k] = 0


def ring_counters() -> Dict[str, int]:
    return dict(_COUNTERS)


def ring_skip_ratio() -> float:
    """executed/naive attention area of everything traced since the last
    reset: 1.0 for the naive schedule, (n+1)/2n for zig-zag at sp=n."""
    if not _COUNTERS["naive_area"]:
        return 1.0
    return _COUNTERS["executed_area"] / _COUNTERS["naive_area"]


def resolve_schedule(schedule: Optional[str], seq_len: int, n: int,
                     causal: bool = True) -> str:
    """The schedule actually run: the explicit arg, else
    ``DEFAULT_SCHEDULE``; downgrades to "naive" when zig-zag can't apply
    (non-causal attention skips nothing; the layout needs ``T % 2n ==
    0``)."""
    if schedule is None:
        schedule = DEFAULT_SCHEDULE
    if schedule not in SCHEDULES:
        raise ValueError(
            f"unknown ring schedule {schedule!r} (one of {SCHEDULES})"
        )
    if schedule == "zigzag" and (not causal or n < 2 or seq_len % (2 * n)):
        schedule = "naive"
    return schedule


def zigzag_permutation(seq_len: int, n: int) -> np.ndarray:
    """Gather indices mapping the natural sequence order to the zig-zag
    shard layout: position block ``r`` of the permuted sequence holds
    chunks ``(r, 2n-1-r)`` of the original. An involution it is not —
    invert with :func:`inverse_permutation`."""
    assert seq_len % (2 * n) == 0, (seq_len, n)
    c = seq_len // (2 * n)
    idx = [
        np.arange(r * c, (r + 1) * c)
        for rank in range(n)
        for r in (rank, 2 * n - 1 - rank)
    ]
    return np.concatenate(idx)


def inverse_permutation(perm: np.ndarray) -> np.ndarray:
    inv = np.empty_like(perm)
    inv[perm] = np.arange(perm.size)
    return inv


@dataclass(frozen=True)
class RingCtx:
    """Ring parameters for callers already inside a manual shard_map region
    over ``axis_name`` (the PP∘SP pipeline stages): ``n`` is the static
    ring size."""
    axis_name: str
    n: int
    schedule: str


def _block_attention_online(
    q,  # [B, Tq, Hkv, G, D] (grouped query heads)
    k,  # [B, Tk, Hkv, D]
    v,  # [B, Tk, Hkv, D]
    mask,  # [B, Tq, Tk] bool
    scale: float,
    m,  # [B, Hkv, G, Tq] running max
    l,  # [B, Hkv, G, Tq] running denom
    acc,  # [B, Tq, Hkv, G, D] running numerator
):
    _COUNTERS["block_calls"] += 1
    _COUNTERS["executed_area"] += int(q.shape[1]) * int(k.shape[1])
    scores = jnp.einsum("btkgd,bskd->bkgts", (q * scale).astype(jnp.float32),
                        k.astype(jnp.float32))
    scores = jnp.where(mask[:, None, None, :, :], scores, _NEG_INF)
    blk_m = jnp.max(scores, axis=-1)
    new_m = jnp.maximum(m, blk_m)
    # guard fully-masked rows (new_m == -inf): keep them at zero weight
    safe_m = jnp.where(new_m <= _NEG_INF / 2, 0.0, new_m)
    alpha = jnp.exp(m - safe_m) * (m > _NEG_INF / 2)
    p = jnp.exp(scores - safe_m[..., None]) * (scores > _NEG_INF / 2)
    new_l = l * alpha + jnp.sum(p, axis=-1)
    blk_out = jnp.einsum("bkgts,bskd->btkgd", p, v.astype(jnp.float32))
    new_acc = acc * alpha.transpose(0, 3, 1, 2)[..., None] + blk_out
    return new_m, new_l, new_acc


def _seg_mask(q_seg, kv_seg):
    """[B, Tq, Tk] same-segment mask with padding (segment 0) excluded."""
    return (kv_seg[:, None, :] == q_seg[:, :, None]) & (q_seg[:, :, None] > 0)


def _finish(acc, l, B, Tq, Hq, D):
    denom = jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
    return (acc / denom).reshape(B, Tq, Hq, D)


def _ring_local_naive(q, k, v, q_seg, axis_name, n, my, causal, scale):
    """The v1 contiguous schedule, kept verbatim as the parity oracle:
    every step runs the full Tl×Tl block with causal+segment masking and
    rotates afterwards. Shapes are the LOCAL shards: q [B, Tl, Hq, D],
    k/v [B, Tl, Hkv, D], q_seg [B, Tl]."""
    B, Tl, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    _COUNTERS["naive_area"] += n * Tl * Tl

    qg = q.reshape(B, Tl, Hkv, G, D)
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, Tl), 1)
    q_cols = my * Tl + cols  # [1, Tl] global columns of resident q

    m0 = jnp.full((B, Hkv, G, Tl), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, Tl), jnp.float32)
    acc0 = jnp.zeros((B, Tl, Hkv, G, D), jnp.float32)

    perm = [(j, (j + 1) % n) for j in range(n)]

    def step(i, carry):
        k_blk, v_blk, seg_blk, m, l, acc = carry
        src = (my - i) % n  # ring position this KV block originated from
        kv_cols = src * Tl + cols
        mask = _seg_mask(q_seg, seg_blk)
        if causal:
            mask = mask & (q_cols[:, :, None] >= kv_cols[:, None, :])
        m, l, acc = _block_attention_online(
            qg, k_blk, v_blk, mask, scale, m, l, acc
        )
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        seg_blk = jax.lax.ppermute(seg_blk, axis_name, perm)
        return k_blk, v_blk, seg_blk, m, l, acc

    # step 0's KV block is the shard's own: kv_seg == q_seg.
    carry = (k, v, q_seg, m0, l0, acc0)
    for i in range(n):  # static unroll: n is the mesh axis size
        carry = step(i, carry)
    _, _, _, m, l, acc = carry
    return _finish(acc, l, B, Tl, Hq, D).astype(q.dtype)


def _ring_local_zigzag(q, k, v, q_seg, axis_name, n, my, scale):
    """The production schedule (causal only). The local shard is two
    chunks of c = Tl/2: early (global chunk ``my``) and late (chunk
    ``2n-1-my``), each with its own online-softmax accumulator. Step 0
    runs the resident diagonal — two half-height calls against the full
    local KV under the real causal mask. Every later step's visiting
    block (origin ``src != my``) decomposes into exactly two fully-visible
    c×c tiles: ``q_late × kv_early`` (kv chunk ``src < n <= 2n-1-my``)
    always, and ``q_early × kv_early`` when ``src < my`` (kv chunk
    ``src < my``) else ``q_late × kv_late`` (kv chunk ``2n-1-src <
    2n-1-my``) — so those tiles need only the segment mask, and the other
    two tiles of the naive schedule are never built. Executed area:
    ``Tl² + (n-1)·Tl²/2 = (n+1)/2n`` of naive's ``n·Tl²``."""
    B, Tl, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    c = Tl // 2
    _COUNTERS["naive_area"] += n * Tl * Tl

    qg = q.reshape(B, Tl, Hkv, G, D)
    qg_e, qg_l = qg[:, :c], qg[:, c:]
    seg_e, seg_l = q_seg[:, :c], q_seg[:, c:]

    # Global columns of the local zig-zag layout (my is traced; the mask
    # contents are data, only the tile structure must be static).
    j = jnp.arange(Tl, dtype=jnp.int32)
    gcols = jnp.where(j < c, my * c + j, (2 * n - 1 - my) * c + (j - c))

    def fresh():
        m = jnp.full((B, Hkv, G, c), _NEG_INF, jnp.float32)
        l = jnp.zeros((B, Hkv, G, c), jnp.float32)
        acc = jnp.zeros((B, c, Hkv, G, D), jnp.float32)
        return m, l, acc

    m_e, l_e, acc_e = fresh()
    m_l, l_l, acc_l = fresh()

    perm = [(r, (r + 1) % n) for r in range(n)]
    k_cur, v_cur, s_cur = k, v, q_seg
    for i in range(n):  # static unroll: n is the mesh axis size
        if i + 1 < n:
            # Double buffering: the rotation for step i+1 is issued before
            # this step's compute, which does not depend on it — the
            # latency-hiding scheduler overlaps transfer with the einsums.
            k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
            v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
            s_nxt = jax.lax.ppermute(s_cur, axis_name, perm)
        if i == 0:
            # Resident diagonal: both q halves against the full local KV
            # under the true causal mask (the only step that needs one).
            causal_e = gcols[:c][None, :, None] >= gcols[None, None, :]
            causal_l = gcols[c:][None, :, None] >= gcols[None, None, :]
            m_e, l_e, acc_e = _block_attention_online(
                qg_e, k_cur, v_cur, _seg_mask(seg_e, s_cur) & causal_e,
                scale, m_e, l_e, acc_e,
            )
            m_l, l_l, acc_l = _block_attention_online(
                qg_l, k_cur, v_cur, _seg_mask(seg_l, s_cur) & causal_l,
                scale, m_l, l_l, acc_l,
            )
        else:
            src = (my - i) % n
            k_be, k_bl = k_cur[:, :c], k_cur[:, c:]
            v_be, v_bl = v_cur[:, :c], v_cur[:, c:]
            ks_e, ks_l = s_cur[:, :c], s_cur[:, c:]
            # Tile 1 — resident late rows × visiting early chunk: fully
            # causally visible for every src, segment mask only.
            m_l, l_l, acc_l = _block_attention_online(
                qg_l, k_be, v_be, _seg_mask(seg_l, ks_e),
                scale, m_l, l_l, acc_l,
            )
            # Tile 2 — which q/kv halves pair up depends on the (traced)
            # origin, but either pairing is fully visible; select the
            # operands and the matching accumulator with where.
            low = src < my
            qs = jnp.where(low, qg_e, qg_l)
            kk = jnp.where(low, k_be, k_bl)
            vv = jnp.where(low, v_be, v_bl)
            qsg = jnp.where(low, seg_e, seg_l)
            ksg = jnp.where(low, ks_e, ks_l)
            m_s = jnp.where(low, m_e, m_l)
            l_s = jnp.where(low, l_e, l_l)
            a_s = jnp.where(low, acc_e, acc_l)
            m2, l2, a2 = _block_attention_online(
                qs, kk, vv, _seg_mask(qsg, ksg), scale, m_s, l_s, a_s,
            )
            m_e = jnp.where(low, m2, m_e)
            l_e = jnp.where(low, l2, l_e)
            acc_e = jnp.where(low, a2, acc_e)
            m_l = jnp.where(low, m_l, m2)
            l_l = jnp.where(low, l_l, l2)
            acc_l = jnp.where(low, acc_l, a2)
        if i + 1 < n:
            k_cur, v_cur, s_cur = k_nxt, v_nxt, s_nxt

    out = jnp.concatenate(
        [_finish(acc_e, l_e, B, c, Hq, D), _finish(acc_l, l_l, B, c, Hq, D)],
        axis=1,
    )
    return out.astype(q.dtype)


def _ring_local(q, k, v, q_seg, axis_name, n, causal, scale, schedule):
    """Schedule dispatch for the per-shard body."""
    my = jax.lax.axis_index(axis_name)
    if schedule == "zigzag" and causal:
        return _ring_local_zigzag(q, k, v, q_seg, axis_name, n, my, scale)
    return _ring_local_naive(q, k, v, q_seg, axis_name, n, my, causal, scale)


def ring_attention_inline(
    q, k, v, segment_ids, ctx: RingCtx,
    causal: bool = True, scale: Optional[float] = None,
):
    """Local-shard ring attention for callers already inside a manual
    shard_map region over ``ctx.axis_name`` (the PP∘SP pipeline stages).
    Shapes are the LOCAL shards; for the zig-zag schedule the layout
    permutation is the caller's responsibility — pipeline_apply_layers
    applies it (and its inverse) globally at the region boundary."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    return _ring_local(
        q, k, v, segment_ids, ctx.axis_name, ctx.n,
        causal, scale, ctx.schedule,
    )


# Why a model's layers (or one layer's kind) cannot run with the sequence
# split over the ring axis, by name.
RING_REFUSALS = {
    "sliding_window": "sliding-window attention is not ring-expressible",
    "state_space_scan": "the chunked state-space scan has no ring form: a "
                        "chunk's entering state is a sum over every chunk "
                        "before it, on whichever rank",
    "gated_delta_rule": "the gated delta rule carries a state matrix a "
                        "value head from chunk to chunk in order: a rank's "
                        "first state is the rank before's last, and its "
                        "convolution reads the rank before's last taps",
    "channel_decay_rule": "the delta rule with a decay a key channel "
                          "carries a state matrix a head from chunk to "
                          "chunk in order, as the gated delta rule does, "
                          "and its three convolutions read the rank "
                          "before's last taps",
    "short_convolution": "a short convolution's taps read the tokens just "
                         "before a token: a rank's first tokens need the "
                         "rank before's last conv_L_cache - 1 of B ⊙ x",
    "learned_sparse_attention": "a learned selection ranks ALL the keys of "
                                "a query's document: a ring step sees a "
                                "slice of them and cannot",
    "selective_scan": "the selective scan (S6) is a recurrence over the "
                      "row's tokens in order: a rank's first state is the "
                      "rank before's last",
}


def ring_refusal(cfg, kind: Optional[str] = None) -> Optional[str]:
    """The name in ``RING_REFUSALS`` of what keeps ring attention off —
    asked of one layer's ``kind``, or (None) of the model: then no layer
    may have a window — or None. A model with a state-space layer is
    refused whole: its other layers would need the sequence split that
    the scan cannot take."""
    if MAMBA in cfg.layer_kinds or SSD in cfg.layer_kinds:
        return "state_space_scan"
    if S6 in cfg.layer_kinds:
        return "selective_scan"
    if GDN in cfg.layer_kinds:
        return "gated_delta_rule"
    if cfg.has_mixer(KDA):
        return "channel_decay_rule"
    if cfg.has_mixer(CONV):
        return "short_convolution"
    if cfg.dsa is not None:
        return "learned_sparse_attention"
    kinds = cfg.layer_kinds if kind is None else (kind,)
    windowed = any(cfg.window_of(k) is not None for k in kinds)
    return "sliding_window" if windowed else None


def ring_eligible(mesh: Optional[Mesh], cfg, batch: int, seq_len: int,
                  axis_name: str = "sp", kind: Optional[str] = None) -> bool:
    """Whether the shapes admit ring attention on this mesh: shard_map
    needs divisible shapes (e.g. generate()'s unbucketed batch dim does
    not divide), and the layers must be ring-expressible
    (:func:`ring_refusal`)."""
    if mesh is None or mesh.shape.get(axis_name, 1) <= 1:
        return False
    return (
        ring_refusal(cfg, kind) is None
        and batch % (mesh.shape["dp"] * mesh.shape["fsdp"]
                     * dict(mesh.shape).get("ep", 1)) == 0
        and seq_len % mesh.shape[axis_name] == 0
        and cfg.n_q_heads % mesh.shape["tp"] == 0
        and cfg.n_kv_heads % mesh.shape["tp"] == 0
    )


def ring_attention(
    q: jnp.ndarray,  # [B, T, Hq, D] — GLOBAL shapes (sharded by GSPMD)
    k: jnp.ndarray,  # [B, T, Hkv, D]
    v: jnp.ndarray,
    segment_ids: jnp.ndarray,  # [B, T]
    mesh: Mesh,
    axis_name: str = "sp",
    causal: bool = True,
    scale: Optional[float] = None,
    schedule: Optional[str] = None,  # None → DEFAULT_SCHEDULE
) -> jnp.ndarray:
    """Context-parallel attention: sequence dim sharded over ``axis_name``."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    T = q.shape[1]
    n = mesh.shape[axis_name]
    schedule = resolve_schedule(schedule, T, n, causal)
    if schedule == "zigzag":
        # Shard-boundary layout permutation: a static gather on the global
        # sequence dim, inverted on the way out — global semantics are
        # untouched, only which rank holds which chunks changes.
        fwd = zigzag_permutation(T, n)
        inv = jnp.asarray(inverse_permutation(fwd))
        fwd = jnp.asarray(fwd)
        q, k, v = (jnp.take(x, fwd, axis=1) for x in (q, k, v))
        segment_ids = jnp.take(segment_ids, fwd, axis=1)
    qkv_spec = P(DATA_AXES, axis_name, "tp", None)
    seg_spec = P(DATA_AXES, axis_name)
    fn = partial(
        _ring_local, axis_name=axis_name, n=n, causal=causal,
        scale=scale, schedule=schedule,
    )
    out = jax.shard_map(
        fn,
        mesh=mesh,
        check_vma=False,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, seg_spec),
        out_specs=qkv_spec,
    )(q, k, v, segment_ids)
    if schedule == "zigzag":
        out = jnp.take(out, inv, axis=1)
    return out
