"""Segment-aware attention for document-packed fixed-shape batches.

Replaces the reference's flash-attn varlen path
(``realhf/impl/model/modules/attn.py:24-27``): instead of 1-D ragged batches,
areal_tpu packs sequences into ``[B, L]`` rows with per-token segment ids
(0 = padding) and uses block-causal same-segment masking — the layout TPU
splash-attention kernels natively support. Pallas kernels back the TPU
path (``areal_tpu/ops/pallas/window_attention.py``: causal self-attention
of a packed row, full or windowed); this module holds the dispatch and the
pure-XLA reference — the CPU's path, the parity tests' oracle, and what
every call the kernel does not take runs.

Shapes: q ``[B, T, Hq, D]``; k, v ``[B, S, Hkv, D]`` with Hq = G * Hkv (GQA).
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
from functools import partial
from typing import Dict, Iterator, Optional

import jax
import jax.numpy as jnp

_NEG_INF = -1e30

# Which implementation each packed_attention call TRACED to, by the label
# of the compiled step it was traced for: {label: {"pallas" | "reference"
# | "window" | "fallback": n}}. "pallas" = the grouped-head kernel under
# a causal mask (a full layer), "window" = the same kernel under a sliding
# window, "fallback" = a TPU kernel was wanted and the O(S^2) reference
# ran instead (a row off the 128-token lane grid, a non-causal call, T !=
# S). Counted at trace time, so it describes the compiled programs;
# chip_smoke.py fails when the train step's fallback count is not zero.
_DISPATCH: Dict[str, collections.Counter] = collections.defaultdict(
    collections.Counter
)
_LABEL = contextvars.ContextVar("attention_dispatch_label",
                               default="unlabelled")


@contextlib.contextmanager
def dispatch_label(label: str) -> Iterator[None]:
    """Attribute the packed_attention calls traced inside the block (a jit
    traces synchronously inside its first call) to ``label``."""
    token = _LABEL.set(label)
    try:
        yield
    finally:
        _LABEL.reset(token)


def active_label() -> str:
    return _LABEL.get()


def count_dispatch(impl: str) -> None:
    _DISPATCH[active_label()][impl] += 1


def dispatch_counts() -> Dict[str, Dict[str, int]]:
    return {label: dict(c) for label, c in _DISPATCH.items()}


def segment_mask(
    q_segment_ids: jnp.ndarray,  # [B, T] int, 0 = padding
    kv_segment_ids: jnp.ndarray,  # [B, S]
    q_positions: Optional[jnp.ndarray] = None,  # [B, T] global position in row
    kv_positions: Optional[jnp.ndarray] = None,  # [B, S]
    causal: bool = True,
    sliding_window: Optional[int] = None,
) -> jnp.ndarray:
    """Boolean mask [B, 1, T, S]: attend iff same (non-zero) segment and,
    when causal, kv position <= q position (and within the sliding window
    when one is configured: q_pos - kv_pos < window, HF mistral semantics)."""
    same = (q_segment_ids[:, :, None] == kv_segment_ids[:, None, :]) & (
        q_segment_ids[:, :, None] > 0
    )
    if causal or sliding_window is not None:
        if q_positions is None:
            q_positions = jnp.arange(q_segment_ids.shape[1])[None, :] * jnp.ones_like(
                q_segment_ids
            )
        if kv_positions is None:
            kv_positions = jnp.arange(kv_segment_ids.shape[1])[None, :] * jnp.ones_like(
                kv_segment_ids
            )
        rel = q_positions[:, :, None] - kv_positions[:, None, :]
        if causal:
            same = same & (rel >= 0)
        if sliding_window is not None:
            same = same & (rel < sliding_window)
    return same[:, None, :, :]


@partial(jax.named_call, name="attention_ref")
def attention_reference(
    q: jnp.ndarray,  # [B, T, Hq, D]
    k: jnp.ndarray,  # [B, S, Hkv, D]
    v: jnp.ndarray,  # [B, S, Hkv, D]
    mask: jnp.ndarray,  # [B, 1, T, S] bool
    scale: Optional[float] = None,
) -> jnp.ndarray:
    B, T, Hq, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    if scale is None:
        scale = D ** -0.5
    qg = q.reshape(B, T, Hkv, G, D)
    D = v.shape[-1]  # the output's width is the value's
    # scores: [B, Hkv, G, T, S]
    scores = jnp.einsum("btkgd,bskd->bkgts", qg * scale, k)
    m = jnp.broadcast_to(mask[:, :, None, :, :], scores.shape)
    scores = jnp.where(m, scores, _NEG_INF)
    # Safe softmax: rows that are fully masked (padding queries) produce zeros.
    smax = jnp.max(scores, axis=-1, keepdims=True)
    unnorm = jnp.exp(scores - jax.lax.stop_gradient(smax)) * m
    denom = jnp.sum(unnorm, axis=-1, keepdims=True)
    probs = unnorm / jnp.maximum(denom, 1e-30)
    out = jnp.einsum("bkgts,bskd->btkgd", probs, v)
    return out.reshape(B, T, Hq, D)


def _wants_kernel(impl: str) -> bool:
    return impl == "pallas" or (
        impl == "auto" and jax.default_backend() == "tpu"
    )


def kernel_padded_len(
    impl: str, length: int, sliding_window: Optional[int] = None,
    head_dim: int = 128, group: int = 1,
) -> Optional[int]:
    """The padded row length at which packed causal self-attention over
    rows of ``length`` tokens runs its Pallas kernel (the grouped-head
    one, under a ``sliding_window`` or without; heads wider than 128 take
    their blocks by ``group``, the query heads a key/value head) — what
    the kernel's output and softmax statistic span; None where
    :func:`packed_attention` takes the XLA reference."""
    if not _wants_kernel(impl):
        return None
    from areal_tpu.ops.pallas import window_attention as wa

    return wa.padded_len(length, sliding_window, head_dim, group)


def packed_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_segment_ids: jnp.ndarray,
    kv_segment_ids: jnp.ndarray,
    q_positions: Optional[jnp.ndarray] = None,
    kv_positions: Optional[jnp.ndarray] = None,
    causal: bool = True,
    sliding_window: Optional[int] = None,
    impl: str = "auto",
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Dispatch between the XLA reference and the Pallas TPU kernel.

    ``impl="auto"`` means the kernel on a TPU and the reference elsewhere.
    The kernel takes a causal call of a packed row over itself (``T ==
    S``) on the 128-token lane grid: the grouped-head kernel — K and V at
    their own head count — under a causal mask (counted as "pallas"), or
    under a ``sliding_window`` a windowed one, which skips the key blocks
    outside the window (counted as "window"). On a TPU nothing quietly
    replaces it: a failed import raises, and every call it does not take
    (a row off the lane grid — a prompt bucket, never a packed training
    row —, a non-causal call, ``T != S``) runs the reference counted as
    "fallback" under the active :func:`dispatch_label`. ``scale`` defaults
    to ``head_dim ** -0.5`` in every implementation."""
    wanted_kernel = _wants_kernel(impl)
    if wanted_kernel:
        from areal_tpu.ops.pallas import window_attention as wa

        if v.shape[-1] > q.shape[-1]:
            # A value wider than q/k (differential attention: 128 over
            # 64): the kernel takes ONE head size, and pads a smaller one
            # to the 128 lanes anyway — q and k get the zeros here.
            if scale is None:
                scale = q.shape[-1] ** -0.5
            wider = [(0, 0)] * 3 + [(0, v.shape[-1] - q.shape[-1])]
            q, k = jnp.pad(q, wider), jnp.pad(k, wider)

    if (wanted_kernel and causal and q.shape[1] == k.shape[1]
            and wa.padded_len(q.shape[1], sliding_window) is not None):
        # A packed row over itself: the grouped-head kernel, K/V at their
        # own head count, under a causal or a windowed mask.
        from areal_tpu.parallel.sharding import current_mesh

        counted, scope = (("pallas", wa.CAUSAL_SCOPE)
                          if sliding_window is None else ("window", wa.SCOPE))
        count_dispatch(counted)
        kernel = partial(wa.window_attention, window=sliding_window,
                         scale=scale)
        mesh = current_mesh()
        with jax.named_scope(scope):
            if mesh is not None and mesh.size > 1:
                return wa.kernel_on_mesh(
                    kernel, mesh, q, k, v, q_segment_ids, kv_segment_ids)
            return kernel(q, k, v, q_segment_ids, kv_segment_ids)
    count_dispatch("fallback" if wanted_kernel else "reference")
    mask = segment_mask(
        q_segment_ids, kv_segment_ids, q_positions, kv_positions, causal,
        sliding_window=sliding_window,
    )
    return attention_reference(q, k, v, mask, scale=scale)


def decode_attention(
    q: jnp.ndarray,  # [B, T, Hq, D] — current step(s); T > 1 = extension
    k_cache: jnp.ndarray,  # [B, S, Hkv, D]
    v_cache: jnp.ndarray,  # [B, S, Hkv, D]
    kv_valid: jnp.ndarray,  # [B, S] bool — or [B, T, S] per-query-token
    scale: Optional[float] = None,  # None = head_dim ** -0.5
) -> jnp.ndarray:
    # A [B, T, S] kv_valid gives each of the T new tokens its own valid
    # set — the causal mask of a multi-token cache extension (prefix
    # seeding, models/generate.extend_state). [B, S] broadcasts the same
    # set over every query token (the single-step decode path).
    if kv_valid.ndim == 3:
        mask = kv_valid[:, None, :, :]  # [B, 1, T, S]
    else:
        mask = kv_valid[:, None, None, :]  # [B, 1, 1, S]
    return attention_reference(q, k_cache, v_cache, mask, scale=scale)


# ---- differential attention (Differential Transformer; phi4flash) ----
#
# q's heads are pairs (2p, 2p+1) = (q1, q2) of pair p, k's heads pairs
# (2j, 2j+1) = (k1, k2), v's heads pairs one value [v1 | v2] of twice the
# head size; q's pair p reads k's and v's pair p // (pairs of q / pairs of
# k). out_p = softmax(q1 k1) v - lambda * softmax(q2 k2) v. Both softmaxes
# of every pair run in ONE attention call over heads laid out (k pair, 1 |
# 2, q pair within it) against k's heads as they are and the value
# repeated for k1 and k2 — grouped-query attention with a value wider
# than q/k, which is what :func:`packed_attention` is handed.

def differential_q(q: jnp.ndarray, n_kv_heads: int) -> jnp.ndarray:
    """[B, T, 2P, D] -> the same heads in the order (k pair, 1 | 2, q pair
    of the k pair), so that consecutive groups of q heads share a k head."""
    B, T, H, D = q.shape
    J = n_kv_heads // 2
    G = H // n_kv_heads
    return q.reshape(B, T, J, G, 2, D).transpose(0, 1, 2, 4, 3, 5).reshape(
        B, T, H, D)


def differential_v(v: jnp.ndarray) -> jnp.ndarray:
    """[B, S, 2J, D] -> [B, S, 2J, 2D]: pair j's value [v1 | v2], once for
    k1 and once for k2."""
    B, S, H, D = v.shape
    v = v.reshape(B, S, H // 2, 1, 2 * D)
    return jnp.broadcast_to(v, (B, S, H // 2, 2, 2 * D)).reshape(
        B, S, H, 2 * D)


def differential_combine(out: jnp.ndarray,  # [B, T, 2P, 2D], differential_q's order
                         n_kv_heads: int,
                         lam: jnp.ndarray,  # scalar
                         lambda_init,  # scalar
                         subln: jnp.ndarray,  # [2D]
                         eps: float) -> jnp.ndarray:
    """``(o1 - lambda o2)``, RMS-normed over the value's width, times
    ``1 - lambda_init``: [B, T, P, 2D] in q's pair order."""
    B, T, H, D2 = out.shape
    J = n_kv_heads // 2
    o = out.reshape(B, T, J, 2, H // n_kv_heads, D2).astype(jnp.float32)
    o = (o[:, :, :, 0] - lam * o[:, :, :, 1]).reshape(B, T, H // 2, D2)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    return (o * subln.astype(jnp.float32) * (1.0 - lambda_init)).astype(
        out.dtype)
