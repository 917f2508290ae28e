"""Memory-lean cross-entropy primitives shared by training and generation.

Role parity: the reference's fused vocab-parallel cross entropy
(``realhf/impl/model/parallelism/tensor_parallel/modules.py:1060-1195``) —
on TPU the fusion comes from XLA (gather + fused logsumexp reduction, no
[B, L, V] f32 materialization) instead of a hand-written kernel; under a
"tp"-sharded vocab GSPMD inserts the same all-reduces Megatron hand-codes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def gather_logprobs(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """log p(labels) per position. logits [..., V], labels [...] → [...] f32.

    Gather + fused logsumexp: logits stay in their compute dtype (bf16 on
    the MXU); only the label-shaped outputs are f32. With a 152k vocab this
    is the difference between fitting in HBM and not.
    """
    with jax.named_scope("xent"):
        tok = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        m = jax.lax.stop_gradient(jnp.max(logits, axis=-1))
        # XLA fuses exp(astype(f32)) into the reduce; the f32 tensor never lands.
        lse = (
            jnp.log(
                jnp.sum(jnp.exp((logits - m[..., None]).astype(jnp.float32)), axis=-1)
            )
            + m.astype(jnp.float32)
        )
        return tok.astype(jnp.float32) - lse
