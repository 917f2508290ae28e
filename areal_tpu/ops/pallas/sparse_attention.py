"""TPU kernels for attention under a LEARNED selection of keys (models/
dsa.py): a lightning indexer scores every earlier token of a query's
document, ``I[t, s] = sum_j w[t, j] * relu(qi[t, j] . ki[s])``, and the
``top_k`` best are the only keys the query attends — a mask that is DATA,
one a block of the model, which no static schedule describes.

Nothing of size T x S ever reaches HBM. The selection is ONE number pair a
query: ``tau``, the ``top_k``-th largest score as a sortable integer, and
``cut``, the row index up to which a score EQUAL to ``tau`` still counts
(ties go to the earlier key). Every kernel that needs the mask makes the
scores of its tile again from ``qi``, ``ki``, ``w`` by the SAME function
(:func:`index_tile`, at the same tile shape: the arithmetic that made ``I``
the first time is the arithmetic that makes it every other time) and
compares them with ``tau`` / ``cut`` (:func:`selected`):

 - :func:`select` — a query tile's scores against all the key tiles its
   documents reach, kept in VMEM as sortable integers; ``tau`` by bisection
   on the integer's 32 bits (count(score >= candidate) a step: exact, no
   sort), ``cut`` by bisection on the row index where a tie straddles the
   cut;
 - :func:`attend_fwd` — flash attention over the key tiles a query tile's
   documents reach, all query heads of a tile in one grid step so that the
   mask of a tile is made once; it also counts the pairs it let through
   (``n_selected``: the device's own count, which the trainer holds against
   the host's ``sum(min(p + 1, top_k))``);
 - :func:`attend_bwd` — dQ (a grid a query tile at a time) and dK / dV (a
   key tile at a time), each making the mask again.

Layouts: q, out, d_out ``[B, Hq, T, D]``; k, v ``[B, Hkv, T, D]``; the
indexer's query ``qi [B, T, Hi * Di]``, its key TRANSPOSED AND TILED
``kit [B, T / bkv, Di, bkv]``, ``w [B, T, Hi]`` float32; per query ``meta
[B, T, 4]`` int32 = (tau, cut, segment id, 0); the keys' segment ids
``kseg [B, T / bkv, 1, bkv]``; softmax statistics ``[B, T, Hq]`` float32
(a head a lane). ``T`` is a multiple of both tiles (the caller pads with
segment id 0). Causality is by row index: packing keeps a document
contiguous in its row.

``interpret=True`` runs the kernels in Pallas's interpreter (the CPU's
tests); tests/test_tpu_compile.py compiles them for a described v5e.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Query and key tiles of every kernel here (the scores of a tile are made
# at this shape wherever they are made).
BQ, BKV = 256, 512
INT_MIN = -(2 ** 31)
_NEG = -1e30
_VMEM = 100 * 1024 * 1024


def sortable(x: jnp.ndarray) -> jnp.ndarray:
    """float32 -> int32 whose signed order is the floats' order."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))


def index_tile(qi: jnp.ndarray,  # [bq, Hi * Di] compute dtype
               kit: jnp.ndarray,  # [Di, bkv] compute dtype
               w: jnp.ndarray,  # [bq, Hi] float32
               n_heads: int) -> jnp.ndarray:
    """The indexer's scores of one tile [bq, bkv], float32: a head at a
    time in a fixed order, each product accumulated in float32 over the
    head's width in one pass — XLA and the kernels run THIS function, so a
    pair's score is the same bits wherever it is made."""
    di = qi.shape[-1] // n_heads
    acc = None
    for j in range(n_heads):
        d = jax.lax.dot_general(
            qi[:, j * di:(j + 1) * di], kit, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        term = w[:, j:j + 1] * jnp.maximum(d, 0.0)
        acc = term if acc is None else acc + term
    return acc


def selected(score_key: jnp.ndarray,  # [bq, bkv] int32, sortable scores
             valid: jnp.ndarray,  # [bq, bkv] bool: causal, same document
             tau: jnp.ndarray, cut: jnp.ndarray,  # [bq, 1] int32
             s_idx: jnp.ndarray) -> jnp.ndarray:
    """The pairs a query attends: above its threshold, or on it at or
    before its cut."""
    return valid & ((score_key > tau) | ((score_key == tau) & (s_idx <= cut)))


def _valid(seg_q, seg_k, t_idx, s_idx):
    return (seg_q == seg_k) & (seg_q > 0) & (s_idx <= t_idx)


def _tile_indices(i, j, bq: int, bkv: int):
    t_idx = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
    s_idx = j * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    return t_idx, s_idx


def _mask_tile(qi, kit, w, meta, kseg, i, j, n_idx_heads: int):
    """bool [bq, bkv]: the selected pairs of query tile i x key tile j."""
    bq, bkv = qi.shape[0], kit.shape[1]
    t_idx, s_idx = _tile_indices(i, j, bq, bkv)
    keys = sortable(index_tile(qi, kit, w, n_idx_heads))
    valid = _valid(meta[:, 2:3], kseg, t_idx, s_idx)
    return selected(keys, valid, meta[:, 0:1], meta[:, 1:2], s_idx)


def tile_ranges(segment_ids: jnp.ndarray, bq: int = BQ, bkv: int = BKV):
    """(lo, hi) [B, T / bq]: the first and last key tile a query tile's
    documents reach (hi < lo: a tile of nothing but padding), and
    (qlo, qhi) [B, T / bkv]: the first and last query tile that reaches a
    key tile. From the row's segment ids alone: a document is contiguous,
    so a query tile reaches back to the start of its first document."""
    B, T = segment_ids.shape
    idx = jnp.arange(T, dtype=jnp.int32)[None]
    real = segment_ids > 0
    first = jnp.concatenate(
        [jnp.ones((B, 1), bool), segment_ids[:, 1:] != segment_ids[:, :-1]],
        axis=1)
    start = jax.lax.cummax(jnp.where(first, idx, 0), axis=1)  # doc start
    big = jnp.int32(T)
    q_start = jnp.where(real, start, big).reshape(B, T // bq, bq).min(-1)
    q_last = jnp.where(real, idx, -1).reshape(B, T // bq, bq).max(-1)
    lo, hi = q_start // bkv, q_last // bkv
    lo = jnp.where(q_last < 0, 0, lo)
    hi = jnp.where(q_last < 0, -1, hi)
    # a key tile is reached by the query tiles from its own first real
    # token to the end of the last document that starts in or before it
    last = jnp.concatenate(
        [segment_ids[:, 1:] != segment_ids[:, :-1], jnp.ones((B, 1), bool)],
        axis=1)
    end = jax.lax.cummin(jnp.where(last, idx, big), axis=1, reverse=True)
    k_first = jnp.where(real, idx, big).reshape(B, T // bkv, bkv).min(-1)
    k_end = jnp.where(real, end, -1).reshape(B, T // bkv, bkv).max(-1)
    qlo, qhi = k_first // bq, k_end // bq
    qlo = jnp.where(k_end < 0, 0, qlo)
    qhi = jnp.where(k_end < 0, -1, qhi)
    return (lo.astype(jnp.int32), hi.astype(jnp.int32),
            qlo.astype(jnp.int32), qhi.astype(jnp.int32))


# ---------------- the selection ----------------

def _select_kernel(lo_ref, hi_ref, qi_ref, kit_ref, w_ref, meta_ref,
                   kseg_ref, out_ref, keys_ref, *, top_k: int,
                   n_idx_heads: int, bq: int, bkv: int, idx_bits: int):
    b, i = pl.program_id(0), pl.program_id(1)
    lo, hi = lo_ref[b, i], hi_ref[b, i]
    qi, w, meta = qi_ref[0], w_ref[0], meta_ref[0]
    seg_q = meta[:, 2:3]
    f32 = jnp.float32
    zeros = jnp.zeros((bq, 1), f32)

    def fill(j, n_valid):
        t_idx, s_idx = _tile_indices(i, j, bq, bkv)
        keys = sortable(index_tile(qi, kit_ref[0, j], w, n_idx_heads))
        valid = _valid(seg_q, kseg_ref[0, j], t_idx, s_idx)
        keys_ref[j] = jnp.where(valid, keys, INT_MIN)
        return n_valid + jnp.sum(valid.astype(f32), axis=1, keepdims=True)

    n_valid = jax.lax.fori_loop(lo, hi + 1, fill, zeros)
    k = jnp.minimum(n_valid, float(top_k))

    def count(pred):  # pairs of each query for which ``pred(keys, s_idx)``
        def body(j, acc):
            _, s_idx = _tile_indices(i, j, bq, bkv)
            return acc + jnp.sum(pred(keys_ref[j], s_idx).astype(f32),
                                 axis=1, keepdims=True)

        return jax.lax.fori_loop(lo, hi + 1, body, zeros)

    def count_ge(cand):
        return count(lambda keys, _: keys >= cand)

    # at most top_k keys: all of them (nothing is on or under INT_MIN)
    out_ref[0] = meta
    out_ref[0, :, 0:1] = jnp.full((bq, 1), INT_MIN, jnp.int32)
    out_ref[0, :, 1:2] = jnp.full((bq, 1), 2 ** 31 - 1, jnp.int32)

    @pl.when(jnp.max(n_valid) > float(top_k))
    def _():
        # the largest integer c with count(keys >= c) >= k: its sign, then
        # a bit at a time
        base = jnp.where(count_ge(jnp.zeros((bq, 1), jnp.int32)) >= k,
                         0, INT_MIN).astype(jnp.int32)

        def bit(n, base):
            cand = base | jnp.left_shift(jnp.int32(1), 30 - n)
            return jnp.where(count_ge(cand) >= k, cand, base)

        tau = jax.lax.fori_loop(0, 31, bit, base)
        n_gt = count(lambda keys, _: keys > tau)
        n_eq = count(lambda keys, _: keys == tau)
        need = k - n_gt  # of the pairs ON the threshold, the earliest

        def tie_cut(_):
            # the largest x with count(ties before x) < need is the row
            # index of the need-th tie
            def bit(n, x):
                cand = x | jnp.left_shift(jnp.int32(1), idx_bits - 1 - n)
                before = count(
                    lambda keys, s_idx: (keys == tau) & (s_idx < cand))
                return jnp.where(before < need, cand, x)

            return jax.lax.fori_loop(0, idx_bits, bit,
                                     jnp.zeros((bq, 1), jnp.int32))

        cut = jax.lax.cond(
            jnp.max(n_eq - need) > 0.0, tie_cut,
            lambda _: jnp.full((bq, 1), 2 ** 31 - 1, jnp.int32), 0)
        selects = n_valid > float(top_k)
        out_ref[0, :, 0:1] = jnp.where(selects, tau, INT_MIN)
        out_ref[0, :, 1:2] = jnp.where(selects, cut, 2 ** 31 - 1)


def select(qi, kit, w, segment_ids, top_k: int, n_idx_heads: int,
           interpret: bool = False) -> jnp.ndarray:
    """``meta`` [B, T, 4] int32 = (tau, cut, segment id, 0) of every
    query: what :func:`attend_fwd` / :func:`attend_bwd` compare a tile's
    scores with."""
    B, T, _ = qi.shape
    nkv, di, bkv = kit.shape[1:]
    bq = BQ
    lo, hi, _, _ = tile_ranges(segment_ids, bq, bkv)
    seg = segment_ids.astype(jnp.int32)
    zero = jnp.zeros_like(seg)
    meta_in = jnp.stack([zero, zero, seg, zero], axis=-1)
    kernel = functools.partial(
        _select_kernel, top_k=top_k, n_idx_heads=n_idx_heads, bq=bq, bkv=bkv,
        idx_bits=max(int(T - 1).bit_length(), 1))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, T // bq),
            in_specs=[
                pl.BlockSpec((1, bq, qi.shape[2]), lambda b, i, *_: (b, i, 0)),
                pl.BlockSpec((1, nkv, di, bkv), lambda b, i, *_: (b, 0, 0, 0)),
                pl.BlockSpec((1, bq, w.shape[2]), lambda b, i, *_: (b, i, 0)),
                pl.BlockSpec((1, bq, 4), lambda b, i, *_: (b, i, 0)),
                pl.BlockSpec((1, nkv, 1, bkv), lambda b, i, *_: (b, 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, bq, 4), lambda b, i, *_: (b, i, 0)),
            scratch_shapes=[pltpu.VMEM((nkv, bq, bkv), jnp.int32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, T, 4), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM),
        interpret=interpret,
        name="dsa_select",
    )(lo, hi, qi, kit, w, meta_in, kseg_of(segment_ids, bkv))


def kseg_of(segment_ids: jnp.ndarray, bkv: int = BKV) -> jnp.ndarray:
    B, T = segment_ids.shape
    return segment_ids.astype(jnp.int32).reshape(B, T // bkv, 1, bkv)


def tiled_key(ki: jnp.ndarray, bkv: int = BKV) -> jnp.ndarray:
    """The indexer's key [B, T, Di] transposed and tiled [B, T / bkv, Di,
    bkv]: a tile is a leading index, and a product needs no transpose."""
    B, T, di = ki.shape
    return ki.reshape(B, T // bkv, bkv, di).transpose(0, 1, 3, 2)


# ---------------- attention under the selection ----------------

def _nt(a, b):  # a [m, d] . b [n, d]^T -> [m, n] float32
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _tn(a, b):  # a [m, n]^T . b [m, d] -> [n, d] float32
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _nn(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _fwd_kernel(lo_ref, hi_ref, q_ref, k_ref, v_ref, qi_ref, kit_ref, w_ref,
                meta_ref, kseg_ref, o_ref, lse_ref, nsel_ref, m_s, l_s,
                acc_s, cnt_s, *, n_idx_heads: int, group: int):
    b, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n_heads = q_ref.shape[1]

    @pl.when(j == 0)
    def _():
        m_s[...] = jnp.full(m_s.shape, _NEG, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)
        cnt_s[...] = jnp.zeros(cnt_s.shape, jnp.float32)

    @pl.when((j >= lo_ref[b, i]) & (j <= hi_ref[b, i]))
    def _():
        sel = _mask_tile(qi_ref[0], kit_ref[0, 0], w_ref[0], meta_ref[0],
                         kseg_ref[0, 0], i, j, n_idx_heads)
        cnt_s[...] += jnp.sum(sel.astype(jnp.float32), axis=1, keepdims=True)
        for h in range(n_heads):
            g = h // group
            s = jnp.where(sel, _nt(q_ref[0, h], k_ref[0, g]), _NEG)
            m_prev = m_s[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.where(sel, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_s[h] = alpha * l_s[h] + jnp.sum(p, axis=1, keepdims=True)
            acc_s[h] = alpha * acc_s[h] + _nn(p.astype(v_ref.dtype),
                                              v_ref[0, g])
            m_s[h] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        nsel_ref[0] = cnt_s[...].astype(jnp.int32)
        for h in range(n_heads):
            l = l_s[h]
            some = l > 0.0
            safe = jnp.where(some, l, 1.0)
            o_ref[0, h] = jnp.where(some, acc_s[h] / safe, 0.0).astype(
                o_ref.dtype)
            lse_ref[0, :, h:h + 1] = jnp.where(
                some, m_s[h] + jnp.log(safe), 0.0)


def _clamped(lo_ref, hi_ref, b, i, j):
    """Key tile ``j`` of query tile ``i``, held inside the tiles it runs:
    a step that does not run fetches no new block."""
    lo = lo_ref[b, i]
    return jnp.clip(j, lo, jnp.maximum(hi_ref[b, i], lo))


# Index maps of a grid (row, query tile, key tile) — the forward's and
# dQ's: a query tile's blocks [B, T, ..] / [B, H, T, ..], and the key
# tile's [B, H, T, ..] / [B, T / bkv, ..] held inside the tiles that run.
def _q_map(b, i, j, *_):
    return (b, i, 0)


def _qh_map(b, i, j, *_):
    return (b, 0, i, 0)


def _kv_map(b, i, j, lo, hi):
    return (b, 0, _clamped(lo, hi, b, i, j), 0)


def _kt_map(b, i, j, lo, hi):
    return (b, _clamped(lo, hi, b, i, j), 0, 0)


def attend_fwd(q, k, v, qi, kit, w, meta, segment_ids, n_idx_heads: int,
               interpret: bool = False):
    """(out [B, Hq, T, Dv], lse [B, T, Hq], n_selected [B, T] int32)."""
    B, Hq, T, D = q.shape
    Hkv, Dv = k.shape[1], v.shape[3]
    nkv, di, bkv = kit.shape[1:]
    bq = BQ
    lo, hi, _, _ = tile_ranges(segment_ids, bq, bkv)
    kernel = functools.partial(_fwd_kernel, n_idx_heads=n_idx_heads,
                               group=Hq // Hkv)
    out, lse, nsel = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, T // bq, nkv),
            in_specs=[
                pl.BlockSpec((1, Hq, bq, D), _qh_map),
                pl.BlockSpec((1, Hkv, bkv, D), _kv_map),
                pl.BlockSpec((1, Hkv, bkv, Dv), _kv_map),
                pl.BlockSpec((1, bq, qi.shape[2]), _q_map),
                pl.BlockSpec((1, 1, di, bkv), _kt_map),
                pl.BlockSpec((1, bq, w.shape[2]), _q_map),
                pl.BlockSpec((1, bq, 4), _q_map),
                pl.BlockSpec((1, 1, 1, bkv), _kt_map),
            ],
            out_specs=[
                pl.BlockSpec((1, Hq, bq, Dv), _qh_map),
                pl.BlockSpec((1, bq, Hq), _q_map),
                pl.BlockSpec((1, bq, 1), _q_map),
            ],
            scratch_shapes=[
                pltpu.VMEM((Hq, bq, 1), jnp.float32),
                pltpu.VMEM((Hq, bq, 1), jnp.float32),
                pltpu.VMEM((Hq, bq, Dv), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, T, Dv), q.dtype),
            jax.ShapeDtypeStruct((B, T, Hq), jnp.float32),
            jax.ShapeDtypeStruct((B, T, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM),
        interpret=interpret,
        name="dsa_attend_fwd",
    )(lo, hi, q, k, v, qi, kit, w, meta, kseg_of(segment_ids, bkv))
    return out, lse, nsel[..., 0]


def _dq_kernel(lo_ref, hi_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
               delta_ref, qi_ref, kit_ref, w_ref, meta_ref, kseg_ref, dq_ref,
               acc_s, *, n_idx_heads: int, group: int):
    b, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n_heads = q_ref.shape[1]

    @pl.when(j == 0)
    def _():
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    @pl.when((j >= lo_ref[b, i]) & (j <= hi_ref[b, i]))
    def _():
        sel = _mask_tile(qi_ref[0], kit_ref[0, 0], w_ref[0], meta_ref[0],
                         kseg_ref[0, 0], i, j, n_idx_heads)
        lse, delta = lse_ref[0], delta_ref[0]
        for h in range(n_heads):
            g = h // group
            s = _nt(q_ref[0, h], k_ref[0, g])
            p = jnp.where(sel, jnp.exp(s - lse[:, h:h + 1]), 0.0)
            dp = _nt(do_ref[0, h], v_ref[0, g])
            ds = p * (dp - delta[:, h:h + 1])
            acc_s[h] += _nn(ds.astype(k_ref.dtype), k_ref[0, g])

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        dq_ref[0] = acc_s[...].astype(dq_ref.dtype)


def _dkv_kernel(qlo_ref, qhi_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                delta_ref, qi_ref, kit_ref, w_ref, meta_ref, kseg_ref,
                dk_ref, dv_ref, dk_s, dv_s, *, n_idx_heads: int, group: int):
    b, j, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n_heads = q_ref.shape[1]

    @pl.when(i == 0)
    def _():
        dk_s[...] = jnp.zeros(dk_s.shape, jnp.float32)
        dv_s[...] = jnp.zeros(dv_s.shape, jnp.float32)

    @pl.when((i >= qlo_ref[b, j]) & (i <= qhi_ref[b, j]))
    def _():
        sel = _mask_tile(qi_ref[0], kit_ref[0, 0], w_ref[0], meta_ref[0],
                         kseg_ref[0, 0], i, j, n_idx_heads)
        lse, delta = lse_ref[0], delta_ref[0]
        for h in range(n_heads):
            g = h // group
            q, do = q_ref[0, h], do_ref[0, h]
            s = _nt(q, k_ref[0, g])
            p = jnp.where(sel, jnp.exp(s - lse[:, h:h + 1]), 0.0)
            dv_s[g] += _tn(p.astype(do.dtype), do)
            dp = _nt(do, v_ref[0, g])
            ds = p * (dp - delta[:, h:h + 1])
            dk_s[g] += _tn(ds.astype(q.dtype), q)

    @pl.when(i == pl.num_programs(2) - 1)
    def _():
        dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


def attend_bwd(q, k, v, qi, kit, w, meta, segment_ids, lse, delta, d_out,
               n_idx_heads: int, interpret: bool = False,
               ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(dq, dk, dv) of :func:`attend_fwd`'s ``out`` under the cotangent
    ``d_out``; ``delta = sum(out * d_out)`` a query and head [B, T, Hq]."""
    B, Hq, T, D = q.shape
    Hkv, Dv = k.shape[1], v.shape[3]
    nkv, di, bkv = kit.shape[1:]
    bq = BQ
    lo, hi, qlo, qhi = tile_ranges(segment_ids, bq, bkv)
    kseg = kseg_of(segment_ids, bkv)
    group = Hq // Hkv
    params = dict(n_idx_heads=n_idx_heads, group=group)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **params),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, T // bq, nkv),
            in_specs=[
                pl.BlockSpec((1, Hq, bq, D), _qh_map),
                pl.BlockSpec((1, Hkv, bkv, D), _kv_map),
                pl.BlockSpec((1, Hkv, bkv, Dv), _kv_map),
                pl.BlockSpec((1, Hq, bq, Dv), _qh_map),
                pl.BlockSpec((1, bq, Hq), _q_map),
                pl.BlockSpec((1, bq, Hq), _q_map),
                pl.BlockSpec((1, bq, qi.shape[2]), _q_map),
                pl.BlockSpec((1, 1, di, bkv), _kt_map),
                pl.BlockSpec((1, bq, w.shape[2]), _q_map),
                pl.BlockSpec((1, bq, 4), _q_map),
                pl.BlockSpec((1, 1, 1, bkv), _kt_map),
            ],
            out_specs=pl.BlockSpec((1, Hq, bq, D), _qh_map),
            scratch_shapes=[pltpu.VMEM((Hq, bq, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM),
        interpret=interpret,
        name="dsa_attend_dq",
    )(lo, hi, q, k, v, d_out, lse, delta, qi, kit, w, meta, kseg)

    # a key tile at a time, the query tiles innermost
    def kq_map(b, j, i, qlo, qhi):
        return (b, _clamped(qlo, qhi, b, j, i), 0)

    def kqh_map(b, j, i, qlo, qhi):
        return (b, 0, _clamped(qlo, qhi, b, j, i), 0)

    def k_map(b, j, i, *_):
        return (b, 0, j, 0)

    def ktile_map(b, j, i, *_):
        return (b, j, 0, 0)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **params),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, nkv, T // bq),
            in_specs=[
                pl.BlockSpec((1, Hq, bq, D), kqh_map),
                pl.BlockSpec((1, Hkv, bkv, D), k_map),
                pl.BlockSpec((1, Hkv, bkv, Dv), k_map),
                pl.BlockSpec((1, Hq, bq, Dv), kqh_map),
                pl.BlockSpec((1, bq, Hq), kq_map),
                pl.BlockSpec((1, bq, Hq), kq_map),
                pl.BlockSpec((1, bq, qi.shape[2]), kq_map),
                pl.BlockSpec((1, 1, di, bkv), ktile_map),
                pl.BlockSpec((1, bq, w.shape[2]), kq_map),
                pl.BlockSpec((1, bq, 4), kq_map),
                pl.BlockSpec((1, 1, 1, bkv), ktile_map),
            ],
            out_specs=[
                pl.BlockSpec((1, Hkv, bkv, D), k_map),
                pl.BlockSpec((1, Hkv, bkv, Dv), k_map),
            ],
            scratch_shapes=[pltpu.VMEM((Hkv, bkv, D), jnp.float32),
                            pltpu.VMEM((Hkv, bkv, Dv), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM),
        interpret=interpret,
        name="dsa_attend_dkv",
    )(qlo, qhi, q, k, v, d_out, lse, delta, qi, kit, w, meta, kseg)
    return dq, dk, dv
