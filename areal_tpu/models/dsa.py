"""Learned sparse attention (KeyeVL2's ``sa_config``; the mechanism of
DeepSeek-V3.2-Exp's sparse attention): beside a block's q, k, v a
LIGHTNING INDEXER scores every earlier token of a query's document, and
the ``top_k`` best are the only keys the query attends. ``u`` is the
block's normed residual stream, ``s <= t`` the tokens of t's document::

    qi[t, j] = rope(u[t] · wq)[j]                 Hi heads of Di
    ki[s]    = rope(LayerNorm(u[s] · wk))         ONE head of Di
    w[t]     = (u[t] · ww) · Hi^-1/2 · Di^-1/2    float32
    I[t, s]  = sum_j w[t, j] · relu(qi[t, j] · ki[s])        float32
    S[t]     = every s <= t where there are at most top_k of them,
               else the top_k with the largest I[t, s] (ties: the earlier)
    o[t]     = softmax over s in S[t] of (q[t] · k[s] · scale) v[s]

— every query head and every key/value head of a block under the SAME
``S[t]``. The rotation turns all ``Di`` dims at the block's RoPE base and
the packed positions; the LayerNorm has a weight and a bias, ε 1e-6.

**No gradient reaches the indexer**: a selection is not differentiable,
so ``d loss / d{wq, wk, ww, k_norm}`` is exactly zero (the backward below
returns zeros for them). The indexer's parameters sit under the subtree
``INDEXER`` of a block's parameters, which the optimizer treats as a
buffer (``BUFFER_SUBTREES``; backend/jax_train.add_decayed_weights passes
over it): what a step adds to them is exactly 0. The alignment loss the
publisher of the mechanism trains its indexer with is a recipe, not a
key of any config here: not run (ROADMAP R3 (g)).

**The selection is a number pair a query**, ``tau`` (the ``top_k``-th
largest score as a sortable integer) and ``cut`` (the row index up to
which a score equal to ``tau`` counts): nothing of size T x S is kept from
the forward to the backward, and no [T, S] array of a row reaches HBM on
the kernel path (ops/pallas/sparse_attention.py). The backward makes the
scores again by the arithmetic that made them (``index_tile``) and
compares them with the kept pair, so it selects the pairs the forward did.
What a selection is a function of — ``qi``, ``ki``, ``w`` and the pair —
is kept under EVERY remat entry (``SELECTION``; 2.2 KB a token a block at
16 x 64: a projection made again may differ in its last bit, and a pair on
the threshold would flip; the XLA form keeps the [T, S] mask itself, a
byte a pair, since XLA may contract a multiply-add in one fusion and not
in another), so the selection never runs twice; under
``attention`` / ``matmuls`` the output and the softmax statistic are kept
too (``window_attention.RESIDUALS``).

On the CPU (``impl`` other than the kernel) the same entry runs XLA:
:func:`scores_xla` (the kernels' ``index_tile`` over the whole row),
:func:`select_xla` (``lax.top_k``: ties to the lower index),
:func:`mask_xla`, ``ops/attention.attention_reference``.

Device scopes (base/telemetry.DSA_SCOPES), nested inside the block's
``attention``: ``dsa_index_proj`` (the three projections, the key's norm,
both rotations), ``dsa_index_scores`` (the key's transpose into tiles; on
the XLA path the [T, S] scores — the kernels make their tiles' scores
themselves, inside the two scopes below), ``dsa_select`` (the kernel
``dsa_select``: scores and both bisections), ``dsa_attention`` (the
kernels ``dsa_attend_{fwd,dq,dkv}``). Trace-time counts:
:func:`geometry_counts`, :func:`impl_counts`.
"""

from __future__ import annotations

import collections
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from areal_tpu.models.config import SparseAttnConfig
from areal_tpu.ops import attention as attn_ops
from areal_tpu.ops.pallas import sparse_attention as sk

# Why a model with a learned selection is not decoded, ring-split or
# pipelined here (models/generate.py, parallel/ring.py, parallel/
# pipeline.py refuse it by these names).
DECODE_REFUSAL = (
    "sparse_attention_indexer_cache: a learned selection decodes from a "
    "cache of the indexer's key (one head a token) beside K/V and picks "
    "its top-k keys a step, which no cache here holds")
RING_REFUSAL = "learned_sparse_attention"
PIPELINE_REFUSAL = "learned_sparse_attention"

# The subtree of a block's parameters that holds the indexer, and the
# subtrees the optimizer treats as buffers: no gradient reaches them and
# no weight decay is added (the rule takes a SUBTREE: these are matrices,
# not one leaf's name).
INDEXER = "indexer"
BUFFER_SUBTREES = (INDEXER,)
LN_EPS = 1e-6
# Aux entries of a block that add up over micro-batches and optimizer
# steps, exact int32 counts a layer: the pairs the attention let through
# (the device's own sum over the mask), the causal same-document pairs,
# the queries past ``top_k`` in their document, the real queries.
SUMMED_AUX = ("dsa_selected_pairs", "dsa_causal_pairs",
              "dsa_selecting_queries", "dsa_queries")
# The device adds them in int32, over a row and over a step's micro-batches:
# exact while the step's causal pairs, the largest of the four, stay at or
# under this (~49 M pairs for one document of 9.9k tokens: 43 of them a
# step). Past it the engine drops the step's counts (:func:`counts_fit`).
COUNT_MAX = 2 ** 31 - 1

# Calls per compiled program, counted where they are traced: {(row, padded
# row, q tile, kv tile, top_k): calls}, and which implementation ran them
# {"kernel" | "xla": calls}.
_GEOMETRY: collections.Counter = collections.Counter()
_IMPL: collections.Counter = collections.Counter()


def geometry_counts() -> Dict[Tuple[int, int, int, int, int], int]:
    return dict(_GEOMETRY)


def impl_counts() -> Dict[str, int]:
    return dict(_IMPL)


def is_buffer(path) -> bool:
    """Whether a parameter leaf at ``path`` (jax key path) lies under a
    buffer subtree."""
    return any(getattr(p, "key", None) in BUFFER_SUBTREES for p in path)


def init_indexer_params(sa: SparseAttnConfig, n: int, d: int, key,
                        dtype) -> Dict[str, jnp.ndarray]:
    """``n`` blocks' indexers stacked [n, ...]: matrices N(0, 0.02), the
    key norm's weight 1 and bias 0."""
    kq, kk, kw = jax.random.split(key, 3)

    def nrm(k, shape):
        return (jax.random.normal(k, shape) * 0.02).astype(dtype)

    return {
        "wq": nrm(kq, (n, d, sa.q_dim)),
        "wk": nrm(kk, (n, d, sa.head_dim)),
        "ww": nrm(kw, (n, d, sa.n_heads)),
        "k_norm": jnp.ones((n, sa.head_dim), dtype),
        "k_norm_b": jnp.zeros((n, sa.head_dim), dtype),
    }


def indexer_param_count(sa: SparseAttnConfig, d: int) -> int:
    return d * (sa.q_dim + sa.head_dim + sa.n_heads) + 2 * sa.head_dim


def matmul_widths(sa: SparseAttnConfig) -> int:
    """Widths of the indexer's three matmul outputs (what the remat entry
    ``matmuls`` keeps of it)."""
    return sa.q_dim + sa.head_dim + sa.n_heads


# jax.ad_checkpoint name of what a selection is a function of — the
# indexer's inputs and a query's (tau, cut) — which the layer scan keeps
# under EVERY remat entry (transformer._remat_policy): a projection made
# again may differ in its last bit from the one the forward made, and a
# pair on the threshold would then flip between forward and backward.
SELECTION = "dsa_selection"


def selection_kept_bytes(sa: SparseAttnConfig, itemsize: int) -> int:
    """Bytes a token a block the layer scan keeps for the selection: qi
    and ki in the compute dtype, w in float32, meta's four int32."""
    return (sa.q_dim + sa.head_dim) * itemsize + 4 * sa.n_heads + 16


_wants_kernel = attn_ops._wants_kernel


def padded_len(length: int) -> int:
    """The row length the kernels run a row of ``length`` tokens at: whole
    query and key tiles."""
    tile = max(sk.BQ, sk.BKV)
    return -(-length // tile) * tile


def kernel_padded_len(impl: str, length: int) -> Optional[int]:
    """As ``ops/attention.kernel_padded_len`` for a block under a learned
    selection: the kernels take every row; None where XLA runs."""
    return padded_len(length) if _wants_kernel(impl) else None


def index_inputs(x: jnp.ndarray,  # [B, T, D] the normed stream
                 ip: Dict[str, jnp.ndarray], sa: SparseAttnConfig,
                 positions: Optional[jnp.ndarray], rope):
    """(qi [B, T, Hi * Di], ki [B, T, Di]) in x's dtype and w [B, T, Hi]
    float32."""
    from areal_tpu.models import transformer as tf

    B, T, _ = x.shape
    qi = (x @ ip["wq"]).reshape(B, T, sa.n_heads, sa.head_dim)
    ki = tf.layer_norm(x @ ip["wk"], ip["k_norm"], ip["k_norm_b"], LN_EPS)
    w = (x @ ip["ww"]).astype(jnp.float32) * (
        sa.n_heads ** -0.5 * sa.head_dim ** -0.5)
    if rope is not None:
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
        cos, sin = tf.rope_tables(positions, sa.head_dim, rope)
        qi = tf.apply_rope(qi, cos, sin)
        ki = tf.apply_rope(ki[:, :, None, :], cos, sin)[:, :, 0]
    return qi.reshape(B, T, sa.q_dim), ki, w


# ---------------- the XLA form ----------------

def scores_xla(qi, ki, w, n_heads: int) -> jnp.ndarray:
    """I [B, T, S] float32: the kernels' ``index_tile`` over a whole row."""
    return jax.vmap(lambda a, b, c: sk.index_tile(a, b.T, c, n_heads))(
        qi, ki, w)


def _valid_xla(segment_ids: jnp.ndarray) -> jnp.ndarray:
    T = segment_ids.shape[1]
    idx = jnp.arange(T)
    return ((segment_ids[:, :, None] == segment_ids[:, None, :])
            & (segment_ids[:, :, None] > 0) & (idx[None, :] <= idx[:, None]))


def select_xla(scores: jnp.ndarray, segment_ids: jnp.ndarray,
               top_k: int) -> jnp.ndarray:
    """``meta`` [B, T, 4] int32 = (tau, cut, segment id, 0) a query, as the
    kernel ``dsa_select`` gives it."""
    T = scores.shape[1]
    valid = _valid_xla(segment_ids)
    keys = jnp.where(valid, sk.sortable(scores), sk.INT_MIN)
    kk = min(top_k, T)
    vals, idx = jax.lax.top_k(keys, kk)  # ties: the lower index first
    selects = jnp.sum(valid, axis=-1) > top_k
    tau = vals[..., kk - 1]
    cut = jnp.max(jnp.where(vals == tau[..., None], idx, -1), axis=-1)
    seg = segment_ids.astype(jnp.int32)
    return jnp.stack([
        jnp.where(selects, tau, sk.INT_MIN),
        jnp.where(selects, cut, 2 ** 31 - 1).astype(jnp.int32),
        seg, jnp.zeros_like(seg)], axis=-1)


def mask_xla(scores, meta, segment_ids) -> jnp.ndarray:
    """bool [B, T, S]: the pairs each query attends."""
    T = scores.shape[1]
    s_idx = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), scores.shape)
    return sk.selected(sk.sortable(scores), _valid_xla(segment_ids),
                       meta[..., 0:1], meta[..., 1:2], s_idx)


# What a test reads the masks of the forward and the backward from (a list
# to append ("fwd" | "bwd", mask) to); None outside tests.
RECORD_MASKS: Optional[list] = None


def _record(which: str, mask) -> None:
    if RECORD_MASKS is not None:
        jax.debug.callback(
            lambda m, log=RECORD_MASKS: log.append((which, np.asarray(m))),
            mask)


# ---------------- the entry ----------------
#
# ``how`` (static): ("kernel", n_idx_heads, top_k, interpret) or ("xla",
# n_idx_heads, top_k, False). q is scaled by the caller; q, k, v are
# [B, T, H, D]; returns (out [B, T, Hq, Dv], n_selected [B, T] int32).

def _to_heads_first(x):
    return x.transpose(0, 2, 1, 3)


@partial(jax.custom_vjp, nondiff_argnums=(7,))
def _attend(q, k, v, qi, ki, w, segment_ids, how):
    return _attend_fwd(q, k, v, qi, ki, w, segment_ids, how)[0]


def _attend_fwd(q, k, v, qi, ki, w, segment_ids, how):
    from areal_tpu.ops.pallas.window_attention import RESIDUALS

    form, n_idx, top_k, interpret = how
    qi, ki, w = (checkpoint_name(a, SELECTION) for a in (qi, ki, w))
    if form == "xla":
        with jax.named_scope("dsa_index_scores"):
            scores = scores_xla(qi, ki, w, n_idx)
        with jax.named_scope("dsa_select"):
            meta = select_xla(scores, segment_ids, top_k)
            # XLA may contract a multiply-add in one fusion and not in
            # another, so scores made again can differ in a last bit: this
            # form, the CPU's, keeps the mask itself (a byte a pair)
            mask = checkpoint_name(
                mask_xla(scores, meta, segment_ids), SELECTION)
            _record("fwd", mask)
        with jax.named_scope("dsa_attention"):
            out = attn_ops.attention_reference(
                q, k, v, mask[:, None], scale=1.0)
            n_selected = jnp.sum(mask, axis=-1, dtype=jnp.int32)
        return (out, n_selected), (q, k, v, qi, ki, w, segment_ids, mask,
                                   None, None)
    with jax.named_scope("dsa_index_scores"):
        kit = sk.tiled_key(ki)
    with jax.named_scope("dsa_select"):
        meta = checkpoint_name(
            sk.select(qi, kit, w, segment_ids, top_k, n_idx, interpret),
            SELECTION)
    with jax.named_scope("dsa_attention"):
        out, lse, n_selected = sk.attend_fwd(
            _to_heads_first(q), _to_heads_first(k), _to_heads_first(v), qi,
            kit, w, meta, segment_ids, n_idx, interpret)
        out = checkpoint_name(_to_heads_first(out), RESIDUALS)
        lse = checkpoint_name(lse, RESIDUALS)
    return (out, n_selected), (q, k, v, qi, ki, w, segment_ids, meta, out,
                               lse)


def _attend_bwd(how, res, cts):
    q, k, v, qi, ki, w, segment_ids, meta, out, lse = res
    d_out = cts[0]
    form, n_idx, _, interpret = how
    zeros = (jnp.zeros_like(qi), jnp.zeros_like(ki), jnp.zeros_like(w),
             np.zeros(segment_ids.shape, jax.dtypes.float0))
    if form == "xla":  # ``meta`` is the kept mask here
        mask = meta
        _record("bwd", mask)
        with jax.named_scope("dsa_attention"):
            _, vjp = jax.vjp(
                lambda q, k, v: attn_ops.attention_reference(
                    q, k, v, mask[:, None], scale=1.0), q, k, v)
            return (*vjp(d_out), *zeros)
    with jax.named_scope("dsa_index_scores"):
        kit = sk.tiled_key(ki)
    with jax.named_scope("dsa_attention"):
        delta = jnp.sum(out.astype(jnp.float32) * d_out.astype(jnp.float32),
                        axis=-1)  # [B, T, Hq]
        dq, dk, dv = sk.attend_bwd(
            _to_heads_first(q), _to_heads_first(k), _to_heads_first(v), qi,
            kit, w, meta, segment_ids, lse, delta,
            _to_heads_first(d_out.astype(q.dtype)), n_idx, interpret)
    return (_to_heads_first(dq), _to_heads_first(dk), _to_heads_first(dv),
            *zeros)


_attend.defvjp(_attend_fwd, _attend_bwd)


def sparse_attention(
    q: jnp.ndarray,  # [B, T, Hq, D]
    k: jnp.ndarray,  # [B, T, Hkv, D]
    v: jnp.ndarray,  # [B, T, Hkv, Dv]
    qi: jnp.ndarray, ki: jnp.ndarray, w: jnp.ndarray,  # index_inputs'
    segment_ids: jnp.ndarray,  # [B, T], 0 = padding
    sa: SparseAttnConfig,
    impl: str = "auto",
    scale: Optional[float] = None,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(attention output [B, T, Hq, Dv], n_selected [B, T] int32: the
    pairs each query attended). A padding query selects nothing and
    returns zeros. Counted as ``sparse`` in
    ``ops/attention.dispatch_counts()`` on the kernel and the XLA path
    alike."""
    B, T = q.shape[:2]
    if scale is None:
        scale = q.shape[-1] ** -0.5
    kernel = _wants_kernel(impl) or interpret
    attn_ops.count_dispatch("sparse")
    _IMPL["kernel" if kernel else "xla"] += 1
    T_pad = padded_len(T) if kernel else T
    _GEOMETRY[(T, T_pad, sk.BQ, sk.BKV, sa.top_k)] += 1
    # the indexer takes no gradient: the selection is not differentiable
    qi, ki, w = (jax.lax.stop_gradient(a) for a in (qi, ki, w))
    q = q * jnp.asarray(scale, q.dtype)
    more = T_pad - T
    if more:
        def pad(a):
            return jnp.pad(a, [(0, 0), (0, more)] + [(0, 0)] * (a.ndim - 2))

        q, k, v, qi, ki, w, segment_ids = map(
            pad, (q, k, v, qi, ki, w, segment_ids))
    how = ("kernel" if kernel else "xla", sa.n_heads, sa.top_k, interpret)
    out, n_selected = _attend(q, k, v, qi, ki, w, segment_ids, how)
    return out[:, :T], n_selected[:, :T]


def attend(cfg, x, ip, q, k, v, segment_ids, positions, impl: str, rope,
           ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """A block's attention proper under its learned selection: the
    indexer's inputs from the normed stream ``x``, the selection, the
    attention — and the block's exact counts (``SUMMED_AUX``)."""
    sa = cfg.dsa
    B, T = q.shape[:2]
    if segment_ids is None:
        segment_ids = jnp.ones((B, T), jnp.int32)
    with jax.named_scope("dsa_index_proj"):
        qi, ki, w = index_inputs(x, ip, sa, positions, rope)
    out, n_selected = sparse_attention(
        q, k, v, qi, ki, w, segment_ids, sa, impl,
        scale=cfg.attention_multiplier)
    real = segment_ids > 0
    # a query's causal same-document keys: its row index less its
    # document's first, plus one (packing keeps a document contiguous)
    idx = jnp.arange(T, dtype=jnp.int32)[None]
    first = jnp.concatenate(
        [jnp.ones((B, 1), bool), segment_ids[:, 1:] != segment_ids[:, :-1]],
        axis=1)
    n_causal = jnp.where(
        real, idx - jax.lax.cummax(jnp.where(first, idx, 0), axis=1) + 1, 0)
    i32 = jnp.int32
    counts = {
        "dsa_selected_pairs": jnp.sum(n_selected, dtype=i32),
        "dsa_causal_pairs": jnp.sum(n_causal, dtype=i32),
        "dsa_selecting_queries": jnp.sum(n_causal > sa.top_k, dtype=i32),
        "dsa_queries": jnp.sum(real, dtype=i32),
    }
    return out, counts


def reduce_layers(v: jnp.ndarray) -> jnp.ndarray:
    """A count stacked a layer [n_layers] -> the one count every layer
    gave, or -1 where the layers disagree (the counts are a function of
    the batch's document lengths alone)."""
    return jnp.where(jnp.all(v == v[0]), v[0], -1)


def host_selected_pairs(seqlens, top_k: int) -> int:
    """``sum(min(p + 1, top_k))`` over the positions of documents of
    ``seqlens`` tokens: what ``dsa_selected_pairs`` must read."""
    total = 0
    for n in seqlens:
        n = int(n)
        m = min(n, top_k)
        total += m * (m + 1) // 2 + (n - m) * top_k
    return total


def host_causal_pairs(seqlens) -> int:
    return sum(int(n) * (int(n) + 1) // 2 for n in seqlens)


def counts_fit(seqlens) -> bool:
    """Whether a step over documents of ``seqlens`` tokens keeps the int32
    counts of ``SUMMED_AUX`` exact (no sum wraps)."""
    return host_causal_pairs(seqlens) <= COUNT_MAX
