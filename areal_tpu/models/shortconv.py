"""The doubly gated short convolution of a ``conv`` block (lfm2 /
lfm2_moe), packed rows. One mixer, ``u = norm(h)`` [B, T, D]
(models/transformer.py adds the residual and runs the block's FFN):

    [B | C | x] = u · sc_in               three chunks of D, no bias
    z = B ⊙ x
    c_t = Σ_j w_j ⊙ z_{t-(K-1)+j}         depthwise, causal, K taps, no
                                          bias, NO activation
    y = C ⊙ c
    out = y · sc_out

Nothing here is a head, a state or a position: a token sees the ``K - 1``
tokens before it, through one gate before the taps and one after.

**Packed rows.** A tap counts only where its token lies in the same
document (``ssm.causal_conv``): a document's first token sees itself
alone, in the forward and — the mask is on what is multiplied — in the
backward pass.

**Precision and what the backward keeps.** The two gates and the taps
(:func:`gated_conv`) are one elementwise pass over ``sc_in``'s output:
the products ``B ⊙ x`` and ``w_j ⊙ z`` and their sum are float32, rounded
once to the compute dtype at ``y``. The pass is checkpointed by itself: its
backward keeps ``sc_in``'s output alone (12 KB a token a block in
bfloat16 — what the block's remat entry ``matmuls`` keeps anyway) and
re-runs the gates and the taps rather than keeping ``z``, ``c`` and the
shifted copies.

Device scopes (base/telemetry.SHORTCONV_SCOPES): ``shortconv_in_proj``,
``shortconv`` (both gates and the taps), ``shortconv_out_proj``.
:func:`geometry_counts` is the trace-time count of the convolutions a
compiled program holds.
"""

from __future__ import annotations

import collections
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from areal_tpu.models.config import ShortConvConfig
from areal_tpu.models.ssm import causal_conv

# Why a model with these blocks is not decoded here (models/generate.py
# and transformer.forward refuse it by this name).
DECODE_REFUSAL = (
    "short_conv_decode_state: a short-convolution block decodes from the "
    "last conv_L_cache - 1 tokens of B ⊙ x, which no cache here holds "
    "beside the attention blocks' K/V")

# Convolutions per compiled program, counted where they are traced (as
# ssm.geometry_counts): {(rows, length, channels, taps): calls}.
_GEOMETRY: collections.Counter = collections.Counter()


def geometry_counts() -> Dict[Tuple[int, int, int, int], int]:
    return dict(_GEOMETRY)


def init_shortconv_params(sc: ShortConvConfig, n: int, hidden_dim: int,
                          key: jax.Array, dtype) -> Dict[str, jnp.ndarray]:
    """``n`` stacked mixers (the norm in front is the block's own): the
    two projections as the program draws every matrix, the taps U(±1/√K)
    (a depthwise convolution's default)."""
    k_in, k_conv, k_out = jax.random.split(key, 3)
    bound = sc.kernel ** -0.5

    def nrm(k, shape, scale=0.02):
        return (jax.random.normal(k, shape) * scale).astype(dtype)

    return {
        "sc_in": nrm(k_in, (n, hidden_dim, 3 * hidden_dim)),
        "sc_conv": jax.random.uniform(
            k_conv, (n, sc.kernel, hidden_dim), minval=-bound, maxval=bound
        ).astype(dtype),
        "sc_out": nrm(k_out, (n, hidden_dim, hidden_dim)),
    }


def shortconv_param_count(sc: ShortConvConfig, hidden_dim: int) -> int:
    """Parameters of one mixer, the norm in front not counted."""
    return 4 * hidden_dim * hidden_dim + sc.kernel * hidden_dim


@jax.checkpoint
def gated_conv(bcx: jnp.ndarray,  # [B, T, 3 C]: [B | C | x], sc_in's output
               w: jnp.ndarray,  # [K, C]; w[K-1] multiplies the token itself
               seg: jnp.ndarray,  # [B, T]
               ) -> jnp.ndarray:
    """``C ⊙ conv(B ⊙ x)`` in float32, rounded once to ``bcx``'s dtype;
    the backward re-runs it from ``bcx`` (module docstring)."""
    f32 = jnp.float32
    Bg, Cg, x = jnp.split(bcx, 3, axis=-1)
    z = Bg.astype(f32) * x.astype(f32)
    c = causal_conv(z, w.astype(f32), 0.0, seg)
    return (Cg.astype(f32) * c).astype(bcx.dtype)


def shortconv_mixer(u: jnp.ndarray,  # [B, T, D] the normed residual stream
                    lp: Dict[str, jnp.ndarray],  # this layer's parameters
                    segment_ids: Optional[jnp.ndarray],  # None = one document a row
                    ) -> jnp.ndarray:
    B_, T, D = u.shape
    seg = (jnp.ones((B_, T), jnp.int32) if segment_ids is None
           else segment_ids)
    _GEOMETRY[(B_, T, D, lp["sc_conv"].shape[0])] += 1
    with jax.named_scope("shortconv_in_proj"):
        bcx = u @ lp["sc_in"]
    with jax.named_scope("shortconv"):
        y = gated_conv(bcx, lp["sc_conv"], seg)
    with jax.named_scope("shortconv_out_proj"):
        return y @ lp["sc_out"]
