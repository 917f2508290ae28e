"""Multi-head latent attention's projection path (deepseek_v2 / _v3,
glm4_moe_lite), packed rows: what stands between a block's normed residual
stream ``u`` [B, T, D] and the attention proper, where q, k and v are NOT
three projections of ``u`` (models/transformer.py runs the attention call,
``wo`` and the residual as for any block)::

    c_q            = rms(u · wq_a, q_a_norm)            [B, T, q_lora_rank]
    q              = c_q · wq_b                      H heads of [nope | rope]
                     (no query latent, ``q_lora_rank`` None: q = u · wq, ONE
                     full-rank projection under the same scope)
    [c_kv | k_r]   = u · wkv_a                          kv_lora_rank + rope
    [k_nope | v]   = rms(c_kv, kv_a_norm) · wkv_b       H heads of nope + v
    q = [q_nope | rope(q_rope)]
    k = [k_nope | rope(k_r)]        k_r ONE vector a token, every head's

RoPE (rotate-half, the model's one table of ``qk_rope_head_dim``) turns the
LAST ``rope`` dims of a query head and ``k_r`` once; the norm of the k/v
latent spans ``kv_lora_rank`` only. After the assembly the attention kernel
sees ``q, k, v`` [B, T, H, nope + rope] at scale ``head_dim ** -0.5`` like
any other block's; ``v`` is [B, T, H, v_head_dim], the key's width or
narrower (ops/attention.py hands the kernel both widths). Without a
position embedding (``cos`` None: kimi_linear) nothing is turned and ``k_r``
is the same un-rotated vector for every head.

**What the backward keeps.** Under the remat entry ``matmuls`` the five
matmuls' outputs (``matmul_widths``: the two latents with ``k_r``, and both
up-projections' expansions); under ``attention`` and ``full`` nothing of
this path. The assembled ``q`` and ``k`` — ``k_r`` repeated a head — are
never kept: they are one fusion behind the up-projections (a slice, the
narrow RoPE and a concatenate) and re-made from them.

Device scopes (base/telemetry.MLA_SCOPES): ``mla_q_proj`` (both q matmuls
and the latent's norm), ``mla_kv_down`` (``wkv_a`` and the latent's norm),
``mla_kv_up`` (``wkv_b``), ``mla_assemble`` (the narrow RoPE, the
broadcast, both concatenates). :func:`geometry_counts` is the trace-time
count of the assemblies a compiled program holds.
"""

from __future__ import annotations

import collections
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from areal_tpu.models.config import MLAConfig

# Why a model with latent attention is not decoded here (models/generate.py
# and transformer.forward refuse it by this name).
DECODE_REFUSAL = (
    "latent_attention_decode_cache: latent attention decodes from a cache "
    "of the kv latent and the shared rotary key (kv_lora_rank + "
    "qk_rope_head_dim a token) with the up-projections absorbed into the "
    "query and the output, which no cache here holds")

# Assemblies per compiled program, counted where they are traced (as
# ssm.geometry_counts): {(rows, length, heads, q_lora_rank, kv_lora_rank,
# qk_nope_head_dim, qk_rope_head_dim, v_head_dim): calls}; q_lora_rank 0 =
# no query latent (one full-rank projection).
_GEOMETRY: collections.Counter = collections.Counter()


def geometry_counts() -> Dict[Tuple[int, ...], int]:
    return dict(_GEOMETRY)


def check(mla: MLAConfig, head_dim: int) -> None:
    """The sizes this path runs: the key's width is the model's head
    width, and the value's is no wider."""
    assert mla.qk_head_dim == head_dim, (
        f"head_dim {head_dim} is not qk_nope_head_dim + qk_rope_head_dim "
        f"= {mla.qk_head_dim}")
    assert mla.v_head_dim <= head_dim, (
        f"v_head_dim {mla.v_head_dim} wider than the key's {head_dim}")


def param_shapes(mla: MLAConfig, hidden_dim: int, n_heads: int,
                 ) -> Dict[str, Tuple[int, ...]]:
    """{name: shape} of one block's projection path (``wo`` and the
    block's own norms are the block's); without a query latent ``wq``
    stands for ``wq_a``, its norm and ``wq_b``."""
    query = {"wq": (hidden_dim, n_heads * mla.qk_head_dim)} if (
        mla.q_lora_rank is None) else {
        "wq_a": (hidden_dim, mla.q_lora_rank),
        "q_a_norm": (mla.q_lora_rank,),
        "wq_b": (mla.q_lora_rank, n_heads * mla.qk_head_dim)}
    return {
        **query,
        "wkv_a": (hidden_dim, mla.kv_a_dim),
        "kv_a_norm": (mla.kv_lora_rank,),
        "wkv_b": (mla.kv_lora_rank, n_heads * mla.kv_b_head_dim),
    }


def init_mla_params(mla: MLAConfig, n: int, hidden_dim: int, n_heads: int,
                    key: jax.Array, dtype) -> Dict[str, jnp.ndarray]:
    """``n`` stacked projection paths: matrices as the program draws every
    matrix (N(0, 0.02)), the two latent norms' weights 1."""
    shapes = param_shapes(mla, hidden_dim, n_heads)
    ks = dict(zip(shapes, jax.random.split(key, len(shapes))))
    return {
        name: jnp.ones((n,) + shape, dtype) if len(shape) == 1
        else (jax.random.normal(ks[name], (n,) + shape) * 0.02).astype(dtype)
        for name, shape in shapes.items()}


def mla_param_count(mla: MLAConfig, hidden_dim: int, n_heads: int) -> int:
    """Parameters of one attention branch: the five matrices (``wo`` among
    them) and the two latent norms."""
    return (sum(math.prod(s) for s in
                param_shapes(mla, hidden_dim, n_heads).values())
            + n_heads * mla.v_head_dim * hidden_dim)


def matmul_widths(mla: MLAConfig, n_heads: int) -> int:
    """Widths of the projection path's matmul outputs that the backward
    reads (``wo``'s is the block's): both latents with ``k_r``, and both
    expansions."""
    return ((mla.q_lora_rank or 0) + n_heads * mla.qk_head_dim
            + mla.kv_a_dim + n_heads * mla.kv_b_head_dim)


def flops_per_token(mla: MLAConfig, hidden_dim: int, n_heads: int) -> int:
    """Multiply-adds x 2 of the five matmuls (four without a query
    latent), a token's forward pass."""
    return 2 * (mla_param_count(mla, hidden_dim, n_heads)
                - (mla.q_lora_rank or 0) - mla.kv_lora_rank)


def mla_qkv(x: jnp.ndarray,  # [B, T, D] the normed residual stream
            lp: Dict[str, jnp.ndarray],  # this layer's parameters
            mla: MLAConfig, n_heads: int, eps: float,
            cos: Optional[jnp.ndarray],  # [B, T, rope]; None = no RoPE
            sin: Optional[jnp.ndarray],
            ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(q, k [B, T, H, nope + rope], v [B, T, H, v_head_dim]) (module
    docstring)."""
    # transformer.py imports this module inside its functions
    from areal_tpu.models.transformer import apply_rope, rms_norm

    B, T, _ = x.shape
    nope, r = mla.qk_nope_head_dim, mla.kv_lora_rank
    _GEOMETRY[(B, T, n_heads, mla.q_lora_rank or 0, r, nope,
               mla.qk_rope_head_dim, mla.v_head_dim)] += 1
    with jax.named_scope("mla_q_proj"):
        if mla.q_lora_rank is None:
            q = x @ lp["wq"]
        else:
            q = rms_norm(x @ lp["wq_a"], lp["q_a_norm"], eps) @ lp["wq_b"]
        q = q.reshape(B, T, n_heads, mla.qk_head_dim)
    with jax.named_scope("mla_kv_down"):
        ckv = x @ lp["wkv_a"]
        c_kv = rms_norm(ckv[..., :r], lp["kv_a_norm"], eps)
        k_r = ckv[..., None, r:]  # [B, T, 1, rope]: one a token
    with jax.named_scope("mla_kv_up"):
        kv = (c_kv @ lp["wkv_b"]).reshape(B, T, n_heads, mla.kv_b_head_dim)
    with jax.named_scope("mla_assemble"):
        q_r = q[..., nope:]
        if cos is not None:
            q_r, k_r = apply_rope(q_r, cos, sin), apply_rope(k_r, cos, sin)
        q = jnp.concatenate([q[..., :nope], q_r], axis=-1)
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_r, (B, T, n_heads, mla.qk_rope_head_dim))],
            axis=-1)
        v = kv[..., nope:]
    return q, k, v
