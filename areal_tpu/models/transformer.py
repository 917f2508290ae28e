"""The trainer transformer — pure-pytree, scan-over-layers, GSPMD-ready.

Replaces the reference's ReaLModel (``realhf/impl/model/nn/real_llm_api.py:100``
+ ``real_llm_base.py``: VocabPositionEmbedding, ReaLModelBlock×L, OutputHead)
with an idiomatic-JAX design:

 - Parameters are a plain pytree with **layers stacked on a leading axis**, so
   the forward pass is one ``lax.scan`` over layers — constant compile time in
   depth, and pipeline parallelism can partition the stacked axis.
 - Batches are fixed-shape ``[B, L]`` document-packed with segment ids
   (0 = pad) instead of 1-D ragged varlen — static shapes for XLA.
 - No module classes: ``init_params(cfg, key)`` + ``forward(params, cfg, ...)``
   are pure functions; sharding is applied externally as a PartitionSpec tree
   of the same structure (areal_tpu/parallel/sharding.py).

Supports GQA, RoPE (HF llama-style rotate-half), RMSNorm, gated-SiLU MLP,
optional qk-norm (per head: qwen3; whole vector: olmoe), optional attention
biases (qwen2), tied embeddings,
critic (scalar) head, and a KV-cache decode mode.

Layers need not be alike: ``cfg.layer_kinds`` gives each its kind, and
the scan runs over PERIODS of that pattern (:func:`_scan_layers`) — one
layer a step for a model whose layers are alike, which is every family
but mellum and nemotron_h. Two sorts of pattern:

 - attention kinds (full or sliding-window, each with its own RoPE
   table): every layer is attention + MLP with the same parameter shapes,
   ``params["layers"]`` is ONE tree stacked ``[n_layers, ...]`` (mellum);
 - mixer kinds (``config.MIXER_KINDS``, nemotron_h): a layer is one mixer
   alone, ``h + f(norm(h))`` with ``f`` a Mamba-2 mixer (models/ssm.py),
   an expert layer (models/moe.py) or attention without a position
   embedding — the kinds differ in parameter SHAPES, so
   ``params["layers"]`` is a tree per kind, ``{kind: {name: [n_kind,
   ...]}}``, and a period's layers slice their kind's stack. So is a
   model whose whole blocks differ in their MIXER (phi4flash's S6 / GMU /
   CROSS blocks; granitemoehybrid's SSD blocks — the Mamba-2 mixer, then
   the dense MLP — beside FULL ones; qwen3_next's GDN blocks — a Gated
   DeltaNet mixer, models/gdn.py, then the expert layer — beside FULL
   ones; lfm2_moe's CONV blocks — a doubly gated short convolution,
   models/shortconv.py, then the dense MLP on the leading blocks and the
   expert layer after them — beside FULL ones).
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from areal_tpu.models.config import (
    ATTENTION_FREE_KINDS,
    ATTENTION_ONLY,
    CONV,
    CROSS,
    FULL,
    GDN,
    KDA,
    GMU,
    MAMBA,
    MEMORY,
    MIXER_KINDS,
    MOE_ONLY,
    S6,
    SAMBAY_KINDS,
    SHARED_KV,
    SLIDING,
    SSD,
    RopeConfig,
    TransformerConfig,
    attention_kind,
    has_dense_ffn,
)
from areal_tpu.ops.attention import (
    decode_attention,
    differential_combine,
    differential_q,
    differential_v,
    packed_attention,
)
from areal_tpu.parallel.sharding import constrain, current_mesh

Params = Dict[str, Any]


# ---------------- init ----------------

def _init_mixer_layers(cfg: TransformerConfig, keys, dtype) -> Params:
    """``params["layers"]`` of a hybrid model: a tree per kind, stacked
    over that kind's layers."""
    from areal_tpu.models import moe as moemod
    from areal_tpu.models import ssm as ssmmod

    d, qd, kvd = cfg.hidden_dim, cfg.q_dim, cfg.kv_dim

    def nrm(k, shape, scale=0.02):
        return (jax.random.normal(k, shape) * scale).astype(dtype)

    layers: Params = {}
    n = cfg.n_layers_of(MAMBA)
    if n:
        layers[MAMBA] = {
            "ln": jnp.ones((n, d), dtype),
            **ssmmod.init_mamba_params(cfg.ssm, n, d, keys[10], dtype)}
    n = cfg.n_layers_of(MOE_ONLY)
    if n:
        layers[MOE_ONLY] = {
            "ln": jnp.ones((n, d), dtype),
            **moemod.init_moe_params(cfg, keys[4], dtype, n)}
    n = cfg.n_layers_of(ATTENTION_ONLY)
    if n:
        layers[ATTENTION_ONLY] = {
            "ln": jnp.ones((n, d), dtype),
            "wq": nrm(keys[0], (n, d, qd)),
            "wk": nrm(keys[1], (n, d, kvd)),
            "wv": nrm(keys[2], (n, d, kvd)),
            "wo": nrm(keys[3], (n, qd, d)),
        }
    # whole blocks whose FFN kinds differ: a stack a kind, keyed apart
    blocks = [k for k in dict.fromkeys(cfg.layer_kinds)
              if k not in MIXER_KINDS]
    for j, kind in enumerate(blocks):
        layers[kind] = _init_block_layers(
            cfg, cfg.n_layers_of(kind),
            jax.random.split(jax.random.fold_in(keys[11], j), 16), dtype,
            dense_ffn=has_dense_ffn(kind), kind=kind)
    return layers


def _init_block_layers(cfg: TransformerConfig, n: int, keys, dtype,
                       dense_ffn: bool, kind: str = FULL,
                       ) -> Dict[str, jnp.ndarray]:
    """``n`` whole blocks (a mixer + FFN) stacked ``[n, ...]``: attention,
    or by ``kind`` an S6 mixer, a Mamba-2 mixer (SSD), a gated memory unit
    (two matrices and no scan), a doubly gated short convolution (CONV)
    or cross attention (q and o alone: its K/V are another layer's); the FFN is the expert layer where the model has one, unless
    ``dense_ffn``."""
    d = cfg.hidden_dim
    qd, kvd, f = cfg.q_dim, cfg.kv_dim, cfg.intermediate_dim
    kind = attention_kind(kind)
    attends = kind not in ATTENTION_FREE_KINDS
    one = _norm_init(cfg)

    def nrm(k, shape, scale=0.02):
        return (jax.random.normal(k, shape) * scale).astype(dtype)

    layers: Dict[str, jnp.ndarray] = {
        "ln1": one((n, d), dtype),
        "ln2": one((n, d), dtype),
    }
    if kind == S6:
        from areal_tpu.models import ssm as ssmmod

        layers.update(ssmmod.init_s6_params(cfg.s6, n, d, keys[13], dtype))
    elif kind == SSD:
        from areal_tpu.models import ssm as ssmmod

        layers.update(ssmmod.init_mamba_params(cfg.ssm, n, d, keys[13], dtype))
    elif kind == GDN:
        from areal_tpu.models import gdn as gdnmod

        layers.update(gdnmod.init_gdn_params(cfg.gdn, n, d, keys[13], dtype))
    elif kind == KDA:
        from areal_tpu.models import kda as kdamod

        layers.update(kdamod.init_kda_params(cfg.kda, n, d, keys[13], dtype))
    elif kind == CONV:
        from areal_tpu.models import shortconv

        layers.update(shortconv.init_shortconv_params(
            cfg.shortconv, n, d, keys[13], dtype))
    elif kind == GMU:
        layers["gmu_in"] = nrm(keys[13], (n, d, cfg.s6.d_inner))
        layers["gmu_out"] = nrm(keys[14], (n, cfg.s6.d_inner, d))
    elif cfg.mla is not None:
        from areal_tpu.models import mla as mlamod

        mlamod.check(cfg.mla, cfg.head_dim)
        layers.update(mlamod.init_mla_params(
            cfg.mla, n, d, cfg.n_q_heads, keys[13], dtype))
        layers["wo"] = nrm(keys[3], (n, cfg.o_dim, d))
    else:
        layers["wq"] = nrm(keys[0], (n, d, qd))
        layers["wo"] = nrm(keys[3], (n, qd, d))
        if kind != CROSS:
            layers["wk"] = nrm(keys[1], (n, d, kvd))
            layers["wv"] = nrm(keys[2], (n, d, kvd))
        if cfg.differential_attention:
            # the four vectors of lambda as the Differential Transformer
            # draws them (N(0, 0.1)) and the sub-norm's weight
            for j, name in enumerate(("lambda_q1", "lambda_k1", "lambda_q2",
                                      "lambda_k2")):
                layers[name] = nrm(jax.random.fold_in(keys[15], j),
                                   (n, cfg.head_dim), 0.1)
            layers["subln"] = jnp.ones((n, 2 * cfg.head_dim), dtype)
    if cfg.moe is not None and not dense_ffn:
        from areal_tpu.models import moe as moemod

        layers.update(moemod.init_moe_params(cfg, keys[4], dtype, n))
    elif cfg.mlp_type == "plain":
        layers.update({
            "w_up": nrm(keys[5], (n, d, f)),
            "w_down": nrm(keys[6], (n, f, d)),
            "b_up": jnp.zeros((n, f), dtype),
            "b_down": jnp.zeros((n, d), dtype),
        })
    else:
        layers.update({
            "w_gate": nrm(keys[4], (n, d, f)),
            "w_up": nrm(keys[5], (n, d, f)),
            "w_down": nrm(keys[6], (n, f, d)),
        })
    if cfg.use_attention_bias and attends:
        layers["bq"] = jnp.zeros((n, qd), dtype)
        if kind != CROSS:
            layers["bk"] = jnp.zeros((n, kvd), dtype)
            layers["bv"] = jnp.zeros((n, kvd), dtype)
    if cfg.use_attn_output_bias and attends:
        layers["bo"] = jnp.zeros((n, d), dtype)
    if cfg.use_qk_norm and attends:
        layers["q_norm"] = one((n, cfg.q_norm_dim), dtype)
        layers["k_norm"] = one((n, cfg.k_norm_dim), dtype)
    if cfg.norm_type == "layer":
        layers["ln1_b"] = jnp.zeros((n, d), dtype)
        layers["ln2_b"] = jnp.zeros((n, d), dtype)
    if cfg.gated_attention and attends:
        layers["wg"] = nrm(keys[12], (n, d, qd))
    if cfg.dsa is not None and attends:
        from areal_tpu.models import dsa as dsamod

        assert cfg.mla is None and kind == FULL, (
            "a learned selection over plain full attention only")
        layers[dsamod.INDEXER] = dsamod.init_indexer_params(
            cfg.dsa, n, d, keys[14], dtype)
    if cfg.sandwich_norm:
        layers["ln1_post"] = jnp.ones((n, d), dtype)
        layers["ln2_post"] = jnp.ones((n, d), dtype)
    return layers


def _norm_init(cfg: TransformerConfig):
    """What draws a norm weight that reads as the identity: zeros where the
    family's weights are zero-centred (``x̂ (1 + w)``), else ones."""
    return jnp.zeros if cfg.zero_centered_norm else jnp.ones


def init_params(cfg: TransformerConfig, key: jax.Array) -> Params:
    dtype = jnp.dtype(cfg.dtype)
    n, d = cfg.n_layers, cfg.hidden_dim
    keys = jax.random.split(key, 16)

    def nrm(k, shape, scale=0.02):
        return (jax.random.normal(k, shape) * scale).astype(dtype)

    if cfg.is_hybrid:
        assert not cfg.is_critic
        assert cfg.norm_type == "rms" or not cfg.has_mixer_layers
        params = {
            "embedding": nrm(keys[7], (cfg.vocab_size, d)),
            "layers": _init_mixer_layers(cfg, keys, dtype),
            "final_ln": _norm_init(cfg)((d,), dtype),
        }
        if cfg.norm_type == "layer":
            params["final_ln_b"] = jnp.zeros((d,), dtype)
        if not cfg.tie_word_embeddings:
            params["lm_head"] = nrm(keys[8], (d, cfg.vocab_size))
        return params

    layers = _init_block_layers(cfg, n, keys, dtype, dense_ffn=False)

    params: Params = {
        "embedding": nrm(keys[7], (cfg.vocab_size, d)),
        "layers": layers,
        "final_ln": _norm_init(cfg)((d,), dtype),
    }
    if cfg.norm_type == "layer":
        params["final_ln_b"] = jnp.zeros((d,), dtype)
    if cfg.pos_embedding == "learned":
        assert cfg.max_position_embeddings, (
            "learned position embeddings need max_position_embeddings"
        )
        params["pos_embedding"] = nrm(
            keys[9], (cfg.max_position_embeddings, d)
        )
    if cfg.is_critic:
        params["value_head"] = nrm(keys[8], (d, 1))
    elif not cfg.tie_word_embeddings:
        params["lm_head"] = nrm(keys[8], (d, cfg.vocab_size))
    return params


# ---------------- primitives ----------------

def rms_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float) -> jnp.ndarray:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (w * (x32 * jax.lax.rsqrt(var + eps)).astype(dt)).astype(dt)


def layer_norm(
    x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray, eps: float
) -> jnp.ndarray:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return (w * ((x32 - mu) * jax.lax.rsqrt(var + eps)).astype(dt) + b).astype(dt)


def rms_norm_zero_centered(x: jnp.ndarray, w: jnp.ndarray,
                           eps: float) -> jnp.ndarray:
    """``x̂ (1 + w)``, the product in float32 (qwen3_next)."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)
            * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def _rms(cfg: TransformerConfig, x, w) -> jnp.ndarray:
    """The model's RMSNorm: its weight zero-centred where the family's is."""
    if cfg.zero_centered_norm:
        return rms_norm_zero_centered(x, w, cfg.rms_norm_eps)
    return rms_norm(x, w, cfg.rms_norm_eps)


def _norm(cfg: TransformerConfig, x, lp, key: str) -> jnp.ndarray:
    if cfg.norm_type == "layer":
        return layer_norm(x, lp[key], lp[key + "_b"], cfg.rms_norm_eps)
    return _rms(cfg, x, lp[key])


_ACTIVATIONS = {
    "silu": jax.nn.silu,
    "gelu": lambda x: jax.nn.gelu(x, approximate=False),
    "gelu_tanh": lambda x: jax.nn.gelu(x, approximate=True),
}


def yarn_inv_freq(head_dim: int, rope: RopeConfig) -> jnp.ndarray:
    """YaRN's inverse frequencies [head_dim/2] as ``transformers`` computes
    them (``_compute_yarn_parameters``): dimensions that turn more than
    ``beta_fast`` times over the original context keep their frequency,
    those that turn less than ``beta_slow`` times have it divided by
    ``factor``, and a linear ramp blends the ones between."""
    half = head_dim // 2
    pos_freqs = rope.base ** (
        jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)

    def dim_of(turns: float) -> float:  # the dimension that turns so often
        return (head_dim * math.log(
            rope.original_max_position / (turns * 2 * math.pi))
        ) / (2 * math.log(rope.base))

    low = max(math.floor(dim_of(rope.beta_fast)), 0)
    high = min(math.ceil(dim_of(rope.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001  # transformers' guard against a zero-width ramp
    ramp = jnp.clip(
        (jnp.arange(half, dtype=jnp.float32) - low) / (high - low), 0, 1)
    return (1 - ramp) / pos_freqs + ramp / (rope.factor * pos_freqs)


def rope_tables(
    positions: jnp.ndarray, head_dim: int, rope: Union[float, RopeConfig]
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin [..., head_dim] for HF-style rotate-half RoPE. ``rope``: a
    base (plain RoPE) or a RopeConfig (YaRN where it has a ``factor``:
    blended frequencies, cos and sin times the attention factor)."""
    if isinstance(rope, RopeConfig) and rope.factor is not None:
        inv_freq = yarn_inv_freq(head_dim, rope)
    else:
        base = rope.base if isinstance(rope, RopeConfig) else rope
        inv_freq = 1.0 / (
            base ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
        )
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [..., dh/2]
    emb = jnp.concatenate([angles, angles], axis=-1)
    if isinstance(rope, RopeConfig) and rope.scale != 1.0:
        return jnp.cos(emb) * rope.scale, jnp.sin(emb) * rope.scale
    return jnp.cos(emb), jnp.sin(emb)


def rope_tables_by_kind(
    cfg: TransformerConfig, positions: jnp.ndarray,
) -> Dict[str, Tuple[jnp.ndarray, jnp.ndarray]]:
    """{attention kind: (cos, sin)} for the kinds the model's layers have
    — one table for a model whose layers are alike."""
    if cfg.pos_embedding == "none":
        return {None: (None, None)}
    ropes = {kind: cfg.rope_of(kind) for kind in dict.fromkeys(
        map(attention_kind, cfg.period_kinds))}
    return {
        kind: (None, None) if rope is None  # no position embedding
        else rope_tables(positions, cfg.rotary_dim, rope)
        for kind, rope in ropes.items()
    }


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """x: [B, T, H, Dh]; cos/sin: [B, T, Dr]. Where the table is narrower
    than the head (``Dr < Dh``: partial rotary), the first ``Dr`` dims are
    turned, rotate-half inside them, and the others pass untouched."""
    rd = cos.shape[-1]
    if rd < x.shape[-1]:
        return jnp.concatenate(
            [apply_rope(x[..., :rd], cos, sin), x[..., rd:]], axis=-1)
    c = cos[:, :, None, :].astype(x.dtype)
    s = sin[:, :, None, :].astype(x.dtype)
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * c + rot * s


def _residual(cfg: TransformerConfig, h: jnp.ndarray,
              branch: jnp.ndarray) -> jnp.ndarray:
    """``h + branch``, the branch times ``cfg.residual_multiplier`` where
    the family has one (in float32: one fused multiply-add, the product
    never rounded to the compute dtype by itself)."""
    if cfg.residual_multiplier == 1.0:
        return h + branch
    f32 = jnp.float32
    return (h.astype(f32) + branch.astype(f32) * cfg.residual_multiplier
            ).astype(h.dtype)


# ---------------- one block ----------------

def _block(
    cfg: TransformerConfig,
    h: jnp.ndarray,  # [B, T, D]
    lp: Dict[str, jnp.ndarray],  # this layer's params (leading axis sliced away)
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    segment_ids: Optional[jnp.ndarray],
    positions: Optional[jnp.ndarray],
    cache_kv: Optional[Tuple[jnp.ndarray, jnp.ndarray]],  # ([B,S,Hkv,Dh], ...)
    cache_write_index: Optional[jnp.ndarray],
    kv_valid: Optional[jnp.ndarray],
    attn_impl: str,
    allow_ring: bool = True,
    ring_ctx=None,  # ring.RingCtx — already inside a manual sp region (PP∘SP)
    rng: Optional[jnp.ndarray] = None,  # per-layer key for MoE router jitter
    allow_ep: bool = True,  # False inside manual regions (pipeline stages)
    kind: Optional[str] = None,  # this layer's attention kind (cfg.layer_kinds)
    shared: Optional[Dict[str, Any]] = None,  # what earlier layers handed on
    layer_index=0,  # this layer's index (lambda_init), unless lp["_layer"]
) -> Tuple[jnp.ndarray, Any, Optional[Dict[str, jnp.ndarray]]]:
    """Returns (h, made, aux). ``made`` is what the layer made that another
    may read: an attention layer's (K, V) — the cache's, or a later cross
    layer's, then as the kernel takes them — and an S6 layer's scan
    output, the memory of a later gated memory unit; None otherwise."""
    B, T, D = h.shape
    if kind is None:
        kind = cfg.period_kinds[0]
    if kind in MIXER_KINDS:
        assert cache_kv is None, DECODE_REFUSAL
        return _mixer_block(
            cfg, kind, h, lp, segment_ids, positions, attn_impl, allow_ring,
            ring_ctx)
    akind = attention_kind(kind)
    if isinstance(cos, dict):  # a table per attention kind
        cos, sin = cos[akind], sin[akind]
    if isinstance(kv_valid, dict):
        kv_valid = kv_valid[akind]

    # The jax.named_scope names below are the device-side names of a
    # profiler capture (base/telemetry.DEVICE_SCOPES): metadata on the
    # ops, no change to the program that runs.
    with jax.named_scope("attn_norm"):
        x = _norm(cfg, h, lp, "ln1")
    if akind in ATTENTION_FREE_KINDS:
        assert cache_kv is None, DECODE_REFUSAL
        from areal_tpu.models import ssm as ssmmod

        if akind == GDN:
            from areal_tpu.models import gdn as gdnmod

            attn, new_kv = gdnmod.gdn_mixer(
                x, lp, cfg.gdn, cfg.rms_norm_eps, segment_ids,
                attn_impl), None
        elif akind == KDA:
            from areal_tpu.models import kda as kdamod

            attn, new_kv = kdamod.kda_mixer(
                x, lp, cfg.kda, cfg.rms_norm_eps, segment_ids,
                attn_impl), None
        elif akind == CONV:
            from areal_tpu.models import shortconv

            attn, new_kv = shortconv.shortconv_mixer(x, lp, segment_ids), None
        elif akind == S6:
            attn, new_kv = ssmmod.s6_mixer(x, lp, cfg.s6, segment_ids,
                                           attn_impl)
        elif akind == SSD:
            attn, new_kv = ssmmod.mamba_mixer(
                x, lp, cfg.ssm, cfg.rms_norm_eps, segment_ids,
                attn_impl), None
        else:
            attn, new_kv = ssmmod.gated_memory_unit(
                x, shared[MEMORY], lp), None
        return _block_ffn(cfg, kind, constrain(_residual(cfg, h, attn),
                                               "hidden"), lp,
                          new_kv, segment_ids, rng, allow_ep, ring_ctx,
                          attn_impl, decode=False)
    if cfg.mla is not None:
        # latent attention: q, k, v through their latents, assembled and
        # turned under scopes of their own (models/mla.py)
        from areal_tpu.models import mla as mlamod

        assert cache_kv is None, mlamod.DECODE_REFUSAL
        assert not (cfg.gated_attention or cfg.differential_attention
                    or cfg.use_qk_norm or akind == CROSS)
        rope = cfg.pos_embedding == "rope" and cos is not None
        q, k, v = mlamod.mla_qkv(
            x, lp, cfg.mla, cfg.n_q_heads, cfg.rms_norm_eps,
            cos if rope else None, sin)
    else:
        q, k, v, gate = _qkv(cfg, akind, x, lp, cos, sin, shared, cache_kv)

    counts = None
    if cfg.dsa is not None:
        # a learned selection of keys (models/dsa.py): the indexer beside
        # q, k, v, and attention under the mask it gives
        from areal_tpu.models import dsa as dsamod

        assert cache_kv is None, dsamod.DECODE_REFUSAL
        assert ring_ctx is None, dsamod.RING_REFUSAL
        with jax.named_scope("attention"):
            attn, counts = dsamod.attend(
                cfg, x, lp[dsamod.INDEXER], q, k, v, segment_ids, positions,
                attn_impl, cfg.rope_of(kind)
                if cfg.pos_embedding == "rope" else None)
        new_kv = (k, v)
    else:
        with jax.named_scope("cross_attention" if akind == CROSS
                             else "attention"):
            attn, new_kv = _attend(
                cfg, q, k, v, segment_ids, positions, cache_kv,
                cache_write_index, kv_valid, attn_impl, allow_ring, ring_ctx,
                kind,
            )
    if cfg.differential_attention:
        with jax.named_scope("diff_attn_combine"):
            f32 = jnp.float32
            lam = (jnp.exp(jnp.sum(lp["lambda_q1"].astype(f32)
                                   * lp["lambda_k1"].astype(f32)))
                   - jnp.exp(jnp.sum(lp["lambda_q2"].astype(f32)
                                     * lp["lambda_k2"].astype(f32))))
            layer = lp.get("_layer", layer_index) + cfg.first_layer_index
            lam_init = 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, f32))
            attn = differential_combine(
                attn, cfg.n_kv_heads, lam + lam_init, lam_init, lp["subln"],
                cfg.rms_norm_eps)

    hid = "hidden" if cache_kv is None else "hidden_decode"
    with jax.named_scope("cross_attention" if akind == CROSS else "o_proj"):
        attn = attn.reshape(B, T, cfg.o_dim)
        if cfg.gated_attention:
            with jax.named_scope("attn_gate"):
                attn = attn * jax.nn.sigmoid(gate)
        attn = attn @ lp["wo"]
        if "bo" in lp:
            attn = attn + lp["bo"]
        if cfg.sandwich_norm:
            with jax.named_scope("post_attn_norm"):
                attn = _norm(cfg, attn, lp, "ln1_post")
        h = constrain(_residual(cfg, h, attn), hid)
    h, new_kv, aux = _block_ffn(
        cfg, kind, h, lp, new_kv, segment_ids, rng, allow_ep, ring_ctx,
        attn_impl, decode=cache_kv is not None)
    if counts is not None:  # the selection's exact counts ride the aux
        aux = {**(aux or {}), **counts}
    return h, new_kv, aux


def _qkv(cfg: TransformerConfig, akind: str, x, lp, cos, sin, shared,
         cache_kv):
    """A block's three projections of the normed stream ``x`` (a cross
    layer's K/V are another layer's), the q/k norm, the attention gate's
    logits and RoPE: (q, k, v, gate), heads split."""
    B, T, _ = x.shape
    dh = cfg.head_dim
    gate = None
    with jax.named_scope("cross_attention" if akind == CROSS else "qkv_proj"):
        q = x @ lp["wq"]
        if akind == CROSS:  # K and V are another layer's, as it made them
            assert cache_kv is None, DECODE_REFUSAL
            if "bq" in lp:
                q = q + lp["bq"]
            k, v = shared[SHARED_KV]
        else:
            k = x @ lp["wk"]
            v = x @ lp["wv"]
            if "bq" in lp:
                q = q + lp["bq"]
                k = k + lp["bk"]
                v = v + lp["bv"]
        # The q/k norm spans the whole projected vector (olmoe) or each
        # head (qwen3): before or after the split into heads.
        qk_norm = cfg.use_qk_norm and cfg.qk_norm_extent
        if qk_norm == "proj":
            q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
            k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
        q = q.reshape(B, T, cfg.n_q_heads, dh)
        if akind != CROSS:
            k = k.reshape(B, T, cfg.n_kv_heads, dh)
            v = v.reshape(B, T, cfg.n_kv_heads, dh)
        if qk_norm == "head":
            q = _rms(cfg, q, lp["q_norm"])
            k = _rms(cfg, k, lp["k_norm"])
        if cfg.gated_attention:
            with jax.named_scope("attn_gate"):
                gate = x @ lp["wg"]
        if cfg.differential_attention:
            assert cache_kv is None, DECODE_REFUSAL
            q = differential_q(q, cfg.n_kv_heads)
            if akind != CROSS:
                v = differential_v(v)
    if cfg.pos_embedding == "rope" and cos is not None:
        with jax.named_scope("rope"):
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
    return q, k, v, gate


def _block_ffn(cfg: TransformerConfig, kind: str, h, lp, new_kv,
               segment_ids, rng, allow_ep: bool, ring_ctx, attn_impl: str,
               decode: bool):
    """The second half of a whole block: ``h + ffn(norm(h))``, the FFN the
    dense MLP or the expert layer. Returns :func:`_block`'s triple."""
    B, T, _ = h.shape
    hid = "hidden_decode" if decode else "hidden"
    with jax.named_scope("mlp_norm"):
        x = _norm(cfg, h, lp, "ln2")
    act = _ACTIVATIONS[cfg.hidden_act]
    if cfg.moe is not None and not has_dense_ffn(kind):
        from areal_tpu.models import moe as moemod

        # Expert-parallel path (moe._dispatch_ep): only from GSPMD-auto regions
        # (a pipeline stage is already manual — nested shard_map is
        # rejected there; GSPMD still handles its ep-sharded weights) and
        # only for shard_map-divisible shapes; decode keeps the tolerant
        # single-shard paths (generation never expert-parallels,
        # api/cli_args.validate_config rejects it).
        ep_mesh = current_mesh() if (
            allow_ep and ring_ctx is None and not decode
        ) else None
        if ep_mesh is not None and not moemod.ep_eligible(
                ep_mesh, cfg.moe, B, T):
            ep_mesh = None
        with jax.named_scope("moe"):
            mlp, aux = moemod.moe_mlp(
                x, lp, cfg.moe, rng=rng,
                mask=(segment_ids > 0) if segment_ids is not None else None,
                mesh=ep_mesh, impl=attn_impl,
            )
            if cfg.sandwich_norm:
                with jax.named_scope("post_mlp_norm"):
                    mlp = _norm(cfg, mlp, lp, "ln2_post")
            return constrain(_residual(cfg, h, mlp), hid), new_kv, aux
    with jax.named_scope("mlp"):
        if cfg.mlp_type == "plain":
            mlp = (act(x @ lp["w_up"] + lp["b_up"]) @ lp["w_down"]
                   + lp["b_down"])
        else:
            mlp = (act(x @ lp["w_gate"]) * (x @ lp["w_up"])) @ lp["w_down"]
        if cfg.sandwich_norm:
            with jax.named_scope("post_mlp_norm"):
                mlp = _norm(cfg, mlp, lp, "ln2_post")
        return constrain(_residual(cfg, h, mlp), hid), new_kv, None


# Why a model with mixer-only layers has no decode mode (models/generate.py
# refuses it by this name).
DECODE_REFUSAL = (
    "recurrent_decode_state: a state-space layer decodes from a recurrent "
    "state (its convolution's last taps and S), which no cache here holds "
    "(nor one layer's K/V for the cross layers that read it)")


def decode_refusal(cfg: TransformerConfig) -> Optional[str]:
    """Why this model has no decode mode, by name, or None: the delta-rule
    (Gated DeltaNet, Kimi Delta Attention) or short-convolution blocks'
    own reason where it has them, latent attention's likewise (its cache
    is the latent's, not K/V's), a learned selection's likewise (the
    indexer's key has no cache: ``dsa.DECODE_REFUSAL``,
    ``sparse_attention_indexer_cache``), ``DECODE_REFUSAL`` for any other
    layer no K/V cache can decode."""
    if cfg.has_mixer(KDA):
        from areal_tpu.models.kda import DECODE_REFUSAL as kda_refusal

        return kda_refusal
    if cfg.mla is not None:
        from areal_tpu.models.mla import DECODE_REFUSAL as mla_refusal

        return mla_refusal
    if cfg.dsa is not None:
        from areal_tpu.models.dsa import DECODE_REFUSAL as dsa_refusal

        return dsa_refusal
    if GDN in cfg.layer_kinds:
        from areal_tpu.models.gdn import DECODE_REFUSAL as gdn_refusal

        return gdn_refusal
    if cfg.has_mixer(CONV):
        from areal_tpu.models.shortconv import DECODE_REFUSAL as conv_refusal

        return conv_refusal
    return DECODE_REFUSAL if cfg.has_cacheless_layers else None


def _mixer_block(
    cfg: TransformerConfig, kind: str, h: jnp.ndarray,
    lp: Dict[str, jnp.ndarray], segment_ids, positions, attn_impl: str,
    allow_ring: bool, ring_ctx,
):
    """A layer that is ONE mixer: ``h + f(norm(h))``, ``f`` by ``kind``.
    Same returns as :func:`_block`; only the attention kind has K/V."""
    B, T, D = h.shape
    with jax.named_scope("attn_norm" if kind == ATTENTION_ONLY
                         else "mlp_norm"):
        x = rms_norm(h, lp["ln"], cfg.rms_norm_eps)
    if kind == MAMBA:
        from areal_tpu.models import ssm as ssmmod

        out = ssmmod.mamba_mixer(x, lp, cfg.ssm, cfg.rms_norm_eps,
                                 segment_ids, attn_impl)
        return constrain(_residual(cfg, h, out), "hidden"), None, None
    if kind == MOE_ONLY:
        from areal_tpu.models import moe as moemod

        with jax.named_scope("moe"):
            out, aux = moemod.moe_mlp(
                x, lp, cfg.moe,
                mask=(segment_ids > 0) if segment_ids is not None else None,
                impl=attn_impl)
        return constrain(_residual(cfg, h, out), "hidden"), None, aux
    dh = cfg.head_dim
    with jax.named_scope("qkv_proj"):
        q = (x @ lp["wq"]).reshape(B, T, cfg.n_q_heads, dh)
        k = (x @ lp["wk"]).reshape(B, T, cfg.n_kv_heads, dh)
        v = (x @ lp["wv"]).reshape(B, T, cfg.n_kv_heads, dh)
    with jax.named_scope("attention"):
        attn, new_kv = _attend(
            cfg, q, k, v, segment_ids, positions, None, None, None,
            attn_impl, allow_ring, ring_ctx, kind)
    with jax.named_scope("o_proj"):
        out = attn.reshape(B, T, cfg.q_dim) @ lp["wo"]
    return constrain(_residual(cfg, h, out), "hidden"), new_kv, None


def _attend(
    cfg: TransformerConfig, q, k, v, segment_ids, positions, cache_kv,
    cache_write_index, kv_valid, attn_impl: str, allow_ring: bool, ring_ctx,
    kind: str,
):
    """One block's attention proper (everything between RoPE and the
    output projection): packed / ring attention in packed mode, the cache
    write and decode attention in decode mode; ``kind`` says whether the
    layer sees its window or its whole document; the softmax scale is
    ``cfg.attention_multiplier`` where the family sets one. Returns
    (attn, new_kv)."""
    B, T = q.shape[:2]
    if cache_kv is None:
        from areal_tpu.parallel import ring as ring_mod

        mesh = current_mesh()
        # Ring attention needs shard_map-divisible shapes; shapes that
        # don't divide (e.g. generate()'s unbucketed batch dim) keep the
        # tolerant GSPMD path.
        use_ring = (
            allow_ring
            and segment_ids is not None
            and ring_mod.ring_eligible(mesh, cfg, B, T, kind=kind)
        )
        if allow_ring and ring_ctx is not None:
            # Already inside a manual region over the ring axis (the PP∘SP
            # pipeline stages): run the local ring body directly — a
            # nested shard_map would be rejected there.
            attn = ring_mod.ring_attention_inline(
                q, k, v, segment_ids, ring_ctx,
                scale=cfg.attention_multiplier)
        elif use_ring:
            # Sequence dim sharded → context-parallel ring attention.
            attn = ring_mod.ring_attention(q, k, v, segment_ids, mesh,
                                           scale=cfg.attention_multiplier)
        else:
            attn = packed_attention(
                q, k, v, segment_ids, segment_ids,
                q_positions=positions, kv_positions=positions,
                causal=True, sliding_window=cfg.window_of(kind),
                impl=attn_impl, scale=cfg.attention_multiplier,
            )
        new_kv = (k, v)
    else:
        k_cache, v_cache = cache_kv
        if getattr(cache_write_index, "ndim", 0) == 1:
            # Per-row write slots (continuous batching: rows of the batch
            # sit at different sequence lengths).
            rows = jnp.arange(B)
            if T == 1:
                k_cache = k_cache.at[rows, cache_write_index].set(k[:, 0])
                v_cache = v_cache.at[rows, cache_write_index].set(v[:, 0])
            else:
                # Multi-token extension (prefix seeding): row b's T new
                # tokens land in slots cache_write_index[b] .. +T.
                idx = cache_write_index[:, None] + jnp.arange(T)[None, :]
                k_cache = k_cache.at[rows[:, None], idx].set(k)
                v_cache = v_cache.at[rows[:, None], idx].set(v)
        else:
            k_cache = jax.lax.dynamic_update_slice_in_dim(
                k_cache, k, cache_write_index, axis=1
            )
            v_cache = jax.lax.dynamic_update_slice_in_dim(
                v_cache, v, cache_write_index, axis=1
            )
        attn = decode_attention(q, k_cache, v_cache, kv_valid,
                                scale=cfg.attention_multiplier)
        new_kv = (k_cache, v_cache)

    return attn, new_kv


# ---------------- layer-stack application ----------------

def apply_layer_stack(
    cfg: TransformerConfig,
    h: jnp.ndarray,  # [B, T, D]
    layer_params: Dict[str, jnp.ndarray],  # stacked [L, ...] (any L)
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    segment_ids: Optional[jnp.ndarray],
    positions: Optional[jnp.ndarray],
    attn_impl: str = "auto",
    remat=False,
    allow_ring: bool = True,
    ring_ctx=None,  # ring.RingCtx when inside a manual sp region (PP∘SP)
    rng: Optional[jnp.ndarray] = None,
    allow_ep: bool = True,  # False inside manual regions (pipeline stages)
):
    """Run a stacked layer dict over ``h`` via lax.scan (packed mode, no KV
    out). Returns (h, aux) where aux stacks per-layer MoE scalars ({} for
    dense). Shared by the GSPMD scan path and the pipeline-parallel stages
    (parallel/pipeline.py, which passes each stage's LOCAL slice — plus a
    ``ring_ctx`` under PP∘SP so attention rings inside the stage).

    ``remat``: False (keep everything) | True/"full" (recompute the whole
    layer in backward) | another entry of ``REMAT_ENTRIES`` (what the
    backward finds kept; the train engine picks one per packed grid).

    ``rng``: base key for MoE router input jitter — split per layer and
    scanned alongside the params so each layer perturbs independently.
    ``rng=None`` keeps the original scan body (bit-identical off path)."""

    if rng is not None:
        assert not cfg.is_hybrid, "router jitter under a mixer pattern"
        n_layers = jax.tree_util.tree_leaves(layer_params)[0].shape[0]
        layer_keys = jax.random.split(rng, n_layers)

        def body(kind, h, xs):
            lp, key = xs
            h2, _, aux = _block(
                cfg, h, lp, cos, sin, segment_ids, positions,
                None, None, None, attn_impl, allow_ring=allow_ring,
                ring_ctx=ring_ctx, rng=key, allow_ep=allow_ep, kind=kind,
            )
            return h2, aux

        with jax.named_scope("layer_scan"):
            h, aux = _scan_layers(cfg, body, h, (layer_params, layer_keys),
                                  remat)
        return h, (aux if aux is not None else {})

    def body(kind, h, lp, shared=None):
        h2, made, aux = _block(
            cfg, h, lp, cos, sin, segment_ids, positions,
            None, None, None, attn_impl, allow_ring=allow_ring,
            ring_ctx=ring_ctx, allow_ep=allow_ep, kind=kind, shared=shared,
        )
        # a model whose layers read earlier layers' tensors is handed
        # them, and hands back what this layer made
        return (h2, aux) if shared is None else (h2, aux, made)

    # "layer_scan" names the scan's own work: slicing each layer's
    # parameters out of the stacked arrays and, in the backward pass,
    # writing each layer's gradients back into them.
    with jax.named_scope("layer_scan"):
        h, aux = _scan_layers(cfg, body, h, layer_params, remat)
    return h, (aux if aux is not None else {})


def _scan_layers(cfg: TransformerConfig, layer: Callable, h, xs, remat=False):
    """``lax.scan`` of ``layer(kind, h, x) -> (h, y)`` over the stacked
    per-layer ``xs`` (leading axis = layers, any whole number of periods),
    one PERIOD of ``cfg.period_kinds`` a step: the kind of each layer of a
    period is static, so a sliding-window layer and a full one trace their
    own attention. Each layer is checkpointed by itself (``remat``). A
    period of one layer is the plain scan over layers. Returns (h, ys) with
    ys stacked per layer."""
    kinds = cfg.period_kinds
    if cfg.is_hybrid:
        return _scan_mixer_layers(cfg, layer, h, xs, remat)
    if len(kinds) == 1:
        def body(h, x):
            return layer(kinds[0], h, x)

        return jax.lax.scan(
            _maybe_checkpoint(body, remat, cfg.dsa is not None), h, xs)
    P = len(kinds)
    L = jax.tree_util.tree_leaves(xs)[0].shape[0]
    assert L % P == 0, f"{L} layers are no whole number of {P}-layer periods"
    # Each layer takes its slice of the period's parameters INSIDE its
    # checkpoint: what the backward finds kept is then the scan's own
    # input, not a copy of every layer's weights.
    steps = [
        _maybe_checkpoint(
            lambda h, xp, j=j, kind=kind: layer(
                kind, h, jax.tree.map(lambda a: a[j], xp)), remat)
        for j, kind in enumerate(kinds)
    ]

    def period(h, xp):
        ys = []
        for step in steps:
            h, y = step(h, xp)
            ys.append(y)
        return h, jax.tree.map(lambda *a: jnp.stack(a), *ys)

    h, ys = jax.lax.scan(
        period, h,
        jax.tree.map(lambda a: a.reshape(L // P, P, *a.shape[1:]), xs))
    return h, jax.tree.map(lambda a: a.reshape(L, *a.shape[2:]), ys)


def period_runs(kinds: Tuple[str, ...]) -> Tuple[Tuple[Tuple[str, ...], int], ...]:
    """A period of layer kinds as RUNS ``(unit, n)``: the unit's kinds,
    ``n`` times over — at each position the repetition that covers most
    (``E M E M E M E M E M *`` is ``((E, M), 5), ((*,), 1)``). A run is
    traced once and scanned ``n`` times, so a period's program holds one
    copy of each repeated layer, not ``n``."""
    runs, i = [], 0
    while i < len(kinds):
        best = (kinds[i:i + 1], 1)
        for u in range(1, (len(kinds) - i) // 2 + 1):
            n = 1
            while kinds[i + n * u:i + (n + 1) * u] == kinds[i:i + u]:
                n += 1
            if n > 1 and n * u > len(best[0]) * best[1]:
                best = (kinds[i:i + u], n)
        runs.append(best)
        i += len(best[0]) * best[1]
    return tuple(runs)


def _scan_mixer_layers(cfg: TransformerConfig, layer: Callable, h, xs,
                       remat=False):
    """:func:`_scan_layers` for a hybrid model: ``xs`` is a tree per kind,
    each stacked over that kind's layers. A scan over periods whose body
    is, for each run of the period (:func:`period_runs`), a scan over the
    run's repetitions of its unit; each layer checkpointed by itself
    (``remat``), taking its slice of the unit's parameters inside its
    checkpoint. The stacks are cut into the runs' ``[periods, n, layers of
    the kind in a unit, ...]`` out here, so that every scan's ``xs`` is an
    input of the scan around it and no copy of the weights is kept for
    the backward pass. Returns (h, ys) with ys stacked over the layers
    that return one (the expert layers' aux); None where no layer does.

    **The hand-over** (``cfg.cross_layer_reads``): a tensor made by layer
    i and read by layers j > i — the scan output of ``cfg.memory_source``,
    the K/V of ``cfg.kv_source``. Then ``layer(kind, h, x, shared) -> (h,
    y, made)``: a source layer is a run of its own (never inside a
    repeated unit), what it ``made`` goes into ``shared`` under the name
    ``cfg.handed_on_by`` gives it, and every later layer is handed
    ``shared`` — as an argument of its checkpoint and a constant of its
    run's scan: kept for the backward pass once whatever ``remat`` is, not
    recomputed a reader, and its gradient is the sum over the readers."""
    kinds = cfg.period_kinds
    n_periods = cfg.n_layers // len(kinds)
    hands_over = bool(cfg.cross_layer_reads)
    # a source layer's kind is tagged, so that no repeated unit holds it
    tagged = tuple(k + "#" + cfg.handed_on_by(i) if cfg.handed_on_by(i)
                   else k for i, k in enumerate(kinds))
    runs = tuple((tuple(k.split("#")[0] for k in unit), n)
                 for unit, n in period_runs(tagged))
    if cfg.differential_attention:  # lambda_init reads a layer's index
        xs = {kind: {**tree, "_layer": jnp.asarray(
            [i for i, k in enumerate(cfg.layer_kinds) if k == kind],
            jnp.int32)} for kind, tree in xs.items()}

    xs_runs, used = [], {kind: 0 for kind in xs}
    for unit, n in runs:
        xr = {}
        for kind in dict.fromkeys(unit):
            c, a0 = unit.count(kind), used[kind]
            used[kind] += c * n

            def cut(a, c=c, a0=a0, n=n):
                a = a.reshape(n_periods, a.shape[0] // n_periods,
                              *a.shape[1:])[:, a0:a0 + c * n]
                shape = (n_periods,) + ((n,) if n > 1 else ()) + (c,)
                return a.reshape(shape + a.shape[2:])

            xr[kind] = jax.tree.map(cut, xs[kind])
        xs_runs.append(xr)

    def unit_body(unit, first):
        def step(j, kind):
            i = unit[:j].count(kind)

            def run(h, xu, *shared):
                return layer(kind, h, jax.tree.map(lambda a: a[i], xu[kind]),
                             *shared)

            return _maybe_checkpoint(run, remat)

        steps = [step(j, kind) for j, kind in enumerate(unit)]

        def body(h, xu, *shared):
            ys, made = [], {}
            for j, step in enumerate(steps):
                h, y, *m = step(h, xu, *shared)
                if y is not None:
                    ys.append(y)
                if m and cfg.handed_on_by(first + j):
                    made[cfg.handed_on_by(first + j)] = m[0]
            ys = jax.tree.map(lambda *a: jnp.stack(a), *ys) if ys else None
            return (h, ys, made) if shared else (h, ys)

        return body

    firsts = [sum(len(u) * n for u, n in runs[:r]) for r in range(len(runs))]
    bodies = [unit_body(unit, first) for (unit, _), first in zip(runs, firsts)]

    def period(h, xrs):
        ys, shared = [], {}
        for (_, n), body, xr in zip(runs, bodies, xrs):
            if not hands_over:
                h, y = jax.lax.scan(body, h, xr) if n > 1 else body(h, xr)
            elif n > 1:  # readers only: ``shared`` is the scan's constant
                h, y = jax.lax.scan(
                    lambda h, xu, body=body, shared=dict(shared):
                    body(h, xu, shared)[:2], h, xr)
            else:
                h, y, made = body(h, xr, dict(shared))
                shared.update(made)
            if y is not None:  # [n, layers with a y in the unit, ...]
                ys.append(jax.tree.map(
                    lambda a: a.reshape(-1, *a.shape[2:]), y) if n > 1 else y)
        return h, (jax.tree.map(lambda *a: jnp.concatenate(a), *ys) if ys
                   else None)

    h, ys = jax.lax.scan(period, h, xs_runs)
    if ys is None:
        return h, None
    return h, jax.tree.map(
        lambda a: a.reshape(a.shape[0] * a.shape[1], *a.shape[2:]), ys)


# What a layer's backward pass finds kept from its forward pass, from the
# least kept to the most; each entry keeps what the one before it keeps.
#   full      — the layer's input only: the backward re-runs the layer.
#   attention — and what the attention kernel's backward reads of its
#               forward (its output and softmax statistics, full or
#               windowed): the forward kernel is not re-run. The padded
#               q/k/v it was handed are recomputed; the XLA reference
#               attention keeps nothing.
#   matmuls   — and the outputs of the layer's matmuls (q/k/v, o_proj,
#               gate, up, the router; down's output nobody reads). Norms,
#               rope, silu·up, casts and the kernel's glue are recomputed.
#               The expert layer is never kept: ``ragged_dot`` is not a
#               ``dot_general``.
# No entry keeps everything (that is ``remat=False``): at the benchmark's
# grids it does not fit a 16 GB chip beside the optimizer state (PERF.md
# §6, PR 28).
REMAT_ENTRIES = ("full", "attention", "matmuls")


def _remat_policy(entry: str, selection: bool = False):
    """``selection``: a model under a learned selection of keys
    (models/dsa.py) keeps, under EVERY entry, what a selection is a
    function of — the indexer's inputs and a query's threshold pair — so
    that the backward compares the bits the forward compared."""
    from areal_tpu.ops.pallas.window_attention import RESIDUALS

    policies = jax.checkpoint_policies
    names = ()
    if selection:
        from areal_tpu.models.dsa import SELECTION

        names = (SELECTION,)
    if entry == "full":
        return policies.save_only_these_names(*names) if names else None
    # The grouped-head (splash) kernel names its output and statistic.
    kernels = policies.save_only_these_names(RESIDUALS, *names)
    if entry == "attention":
        return kernels
    if entry == "matmuls":
        return policies.save_from_both_policies(
            kernels, policies.dots_with_no_batch_dims_saveable)
    raise ValueError(f"remat={entry!r}: not one of {REMAT_ENTRIES}")


def _maybe_checkpoint(body, remat, selection: bool = False):
    """``remat``: False (keep everything) | True (= "full") | an entry of
    ``REMAT_ENTRIES``."""
    if not remat:
        return body
    return jax.checkpoint(
        body, policy=_remat_policy("full" if remat is True else remat,
                                   selection))


def _block_matmul_widths(cfg: TransformerConfig, dense_ffn: bool,
                         kind: str = FULL, mixer_only: bool = False) -> int:
    """Widths of a whole block's matmul outputs that its backward reads:
    q/k/v (and the attention gate), o_proj, and the MLP's matmuls into
    the hidden width (gate and up, or up) — nothing in the backward reads
    the last matmul's output, unless a sandwich norm does; an MoE layer
    keeps the router's logits and its shared expert's pair. By ``kind``
    the mixer's are an S6 mixer's (in-projection, [δ | B | C], Δ), a
    Mamba-2 mixer's two projections, a Gated DeltaNet mixer's three, a
    Kimi Delta Attention mixer's five, a short convolution's two, a gated memory unit's one, cross
    attention's q and o, or latent attention's five (the two latents and
    their expansions, never the assembled q and k: models/mla.py).
    ``mixer_only``: the mixer's (the attention branch's) alone."""
    kind = attention_kind(kind)
    if kind == S6:
        widths = 3 * cfg.s6.d_inner + cfg.s6.x_proj_dim + cfg.hidden_dim
    elif kind == SSD:  # the scan's einsums carry batch dimensions
        widths = cfg.ssm.in_proj_dim + cfg.hidden_dim
    elif kind == GDN:  # the rule's einsums carry batch dimensions
        widths = cfg.gdn.qkvz_dim + cfg.gdn.ba_dim + cfg.hidden_dim
    elif kind == KDA:  # both in-projections, both gates, the out-projection
        from areal_tpu.models.kda import matmul_widths as kda_widths

        widths = kda_widths(cfg.kda) + cfg.hidden_dim
    elif kind == CONV:  # [B | C | x], and the out-projection
        widths = 4 * cfg.hidden_dim
    elif kind == GMU:
        widths = cfg.s6.d_inner + cfg.hidden_dim
    elif kind == CROSS:
        widths = cfg.q_dim + cfg.hidden_dim
    elif cfg.mla is not None:  # both latents, both expansions, o_proj
        from areal_tpu.models.mla import matmul_widths

        widths = matmul_widths(cfg.mla, cfg.n_q_heads) + cfg.hidden_dim
    else:
        widths = cfg.q_dim + 2 * cfg.kv_dim + cfg.hidden_dim
    if cfg.gated_attention and kind not in ATTENTION_FREE_KINDS:
        widths += cfg.q_dim
    if cfg.dsa is not None and kind == FULL:  # the indexer's three
        from areal_tpu.models.dsa import matmul_widths as dsa_widths

        widths += dsa_widths(cfg.dsa)
    if mixer_only:
        return widths
    if cfg.sandwich_norm:  # the post-norm reads the FFN's last matmul
        widths += cfg.hidden_dim
    if cfg.moe is None or dense_ffn:
        return widths + (
            1 if cfg.mlp_type == "plain" else 2) * cfg.intermediate_dim
    return widths + (cfg.moe.n_routed + int(cfg.moe.shared_expert_gate)
                     + 2 * (cfg.moe.shared_intermediate_dim or 0))


def remat_kept_bytes(
    cfg: TransformerConfig, tokens: int, itemsize: int,
    full_tokens: int = 0, window_tokens: int = 0,
) -> Dict[str, int]:
    """Bytes the layer scan keeps between its forward and its backward
    pass under each entry of ``REMAT_ENTRIES``, for ``tokens`` tokens in
    a compute dtype of ``itemsize`` bytes. ``full_tokens``: the tokens of
    the attention kernel's output, rows x PADDED length (0 where
    attention takes the XLA reference), on the full-attention layers;
    ``window_tokens`` likewise on the sliding-window layers, whose tile
    and so padded length may differ. Arithmetic on the widths in ``cfg``,
    checked against what jax really keeps in tests/test_remat_plan.py and
    against the chip's compiler in PERF.md §5."""
    from areal_tpu.ops.pallas.window_attention import LANE

    full = tokens * cfg.hidden_dim * itemsize
    # The kernel writes heads padded to the lane width, and one float32
    # statistic a head (a logsumexp).
    lanes = -(-(cfg.o_dim // cfg.n_q_heads) // LANE) * LANE
    n_sliding = cfg.layer_kinds.count(SLIDING)
    per_token = cfg.n_q_heads * (lanes * itemsize + 4)
    causal, window = full_tokens * per_token, window_tokens * per_token
    if cfg.is_hybrid:
        # A mixer layer's matmuls whose outputs its backward reads: the
        # Mamba in-projection (the scan's einsums carry batch dimensions
        # and are never kept); q/k/v; the router's logits, the latent
        # down-projection and the shared expert's first matmul. The last
        # matmul of each mixer feeds the residual sum alone.
        moe = cfg.moe
        widths = {
            MAMBA: cfg.ssm.in_proj_dim if cfg.ssm else 0,
            ATTENTION_ONLY: cfg.q_dim + 2 * cfg.kv_dim,
            MOE_ONLY: (moe.n_routed + (moe.latent_dim or 0)
                       + (moe.shared_intermediate_dim or 0)) if moe else 0,
        }
        kernel = {ATTENTION_ONLY: causal, FULL: causal, CROSS: causal,
                  SLIDING: window}
        for kind in cfg.layer_kinds:  # whole blocks whose FFN kinds differ
            if kind not in MIXER_KINDS:
                widths[kind] = _block_matmul_widths(cfg, has_dense_ffn(kind),
                                                    kind)
        # what one layer hands on to later ones is kept once, whatever
        # the entry: the memory [d_inner] and the K/V as the kernel takes
        # them (the value repeated for each k of a pair)
        reads = cfg.cross_layer_reads
        handed = tokens * itemsize * (
            (cfg.s6.d_inner if MEMORY in reads else 0)
            + (cfg.kv_dim * (3 if cfg.differential_attention else 2)
               if SHARED_KV in reads else 0))
        kept = {"full": cfg.n_layers * full + handed}
        kept["attention"] = kept["full"] + sum(
            kernel.get(attention_kind(kind), 0) for kind in cfg.layer_kinds)
        kept["matmuls"] = kept["attention"] + tokens * itemsize * sum(
            widths[kind] for kind in cfg.layer_kinds)
        return kept
    matmuls = tokens * _block_matmul_widths(cfg, False) * itemsize
    attention = (cfg.n_layers - n_sliding) * causal + n_sliding * window
    if cfg.dsa is not None:  # what a selection is a function of: always
        from areal_tpu.models.dsa import selection_kept_bytes

        full += tokens * selection_kept_bytes(cfg.dsa, itemsize)
    kept = {"full": cfg.n_layers * full}
    kept["attention"] = kept["full"] + attention
    kept["matmuls"] = kept["attention"] + cfg.n_layers * matmuls
    return kept


def attention_kept_bytes_per_token(
    cfg: TransformerConfig, entry, itemsize: int, kernel: bool,
) -> int:
    """Of :func:`remat_kept_bytes`, what ONE full-attention block's
    attention branch keeps a token under ``entry`` (False: no remat, not
    reckoned, 0): nothing under "full"; the kernel's output and statistic
    under "attention" (where the kernel runs: ``kernel``); under "matmuls"
    the branch's matmul outputs too — for latent attention both latents
    and both expansions, never the assembled q and k. The gauge
    ``train/mla_kept_bytes_per_token``."""
    from areal_tpu.ops.pallas.window_attention import LANE

    selection = 0
    if cfg.dsa is not None and entry:  # under every entry
        from areal_tpu.models.dsa import selection_kept_bytes

        selection = selection_kept_bytes(cfg.dsa, itemsize)
    if entry in (False, "full"):
        return selection
    lanes = -(-(cfg.o_dim // cfg.n_q_heads) // LANE) * LANE
    kept = selection + (
        cfg.n_q_heads * (lanes * itemsize + 4) if kernel else 0)
    if entry == "matmuls":
        kept += itemsize * _block_matmul_widths(cfg, False, FULL,
                                                mixer_only=True)
    return kept


# ---------------- forward ----------------

def forward(
    params: Params,
    cfg: TransformerConfig,
    tokens: jnp.ndarray,  # [B, T] int32
    positions: jnp.ndarray,  # [B, T] int32 — per-sequence positions (for RoPE)
    segment_ids: Optional[jnp.ndarray] = None,  # [B, T], 0 = pad (packed mode)
    kv_cache: Optional[Dict[str, jnp.ndarray]] = None,  # decode mode
    cache_write_index: Optional[jnp.ndarray] = None,
    kv_valid=None,  # [B, S] / [B, T, S] bool, or {attention kind: that}
    attn_impl: str = "auto",
    remat=False,  # False | True | an entry of REMAT_ENTRIES
    return_kv: bool = True,  # False in training: don't stack per-layer K/V
    return_aux: bool = False,  # also return MoE aux losses (layer means)
    pp_microbatches: Optional[int] = None,  # pipeline depth (None = auto)
    return_hidden: bool = False,  # skip the head; return final hidden
    rng: Optional[jnp.ndarray] = None,  # MoE router-jitter key (train only)
):
    """Returns (output, kv) — or (output, kv, aux) when ``return_aux`` —
    where output is logits [B, T, V] (or values [B, T] for critics) and kv
    stacks per-layer keys/values [n_layers, B, S, Hkv, Dh] (S = T in packed
    mode, the cache length in decode mode). ``aux`` is a dict of MoE
    balancing scalars averaged over layers ({} for dense models).

    Packed mode: ``segment_ids`` given, no cache — block-causal attention.
    Decode mode: ``kv_cache`` given — T is the new-token count (typically 1),
    cache slots are written at ``cache_write_index`` and attention runs over
    ``kv_valid`` cache slots — one set for every layer, or one per
    attention kind (:func:`kv_valid_by_kind`).
    """
    decode = kv_cache is not None
    if cfg.has_cacheless_layers and (decode or return_kv):
        raise NotImplementedError(decode_refusal(cfg))
    with jax.named_scope("embed"):
        h = params["embedding"][tokens]
        if cfg.scale_embeddings:  # gemma normalizer
            h = h * jnp.asarray(cfg.hidden_dim ** 0.5, h.dtype)
        if cfg.embedding_multiplier != 1.0:  # granite
            h = h * jnp.asarray(cfg.embedding_multiplier, h.dtype)
        if cfg.pos_embedding == "learned":
            h = h + params["pos_embedding"][positions]
        h = constrain(h, "hidden" if not decode else "hidden_decode")
    with jax.named_scope("rope"):
        ropes = rope_tables_by_kind(cfg, positions)
        if len(ropes) == 1:  # layers alike: the one table
            (cos, sin), = ropes.values()
        else:
            cos = {kind: cs[0] for kind, cs in ropes.items()}
            sin = {kind: cs[1] for kind, cs in ropes.items()}
    layer_params = params["layers"]

    # Blocks whose FFN kinds differ (a tree per kind) stack their K/V in
    # layer order like any whole-block model, and give no aux with them:
    # a dense block has none, and inference asks for no balancing loss.
    if decode:
        def body(kind, h, xs):
            lp, (kc, vc) = xs
            h2, (kc2, vc2), aux = _block(
                cfg, h, lp, cos, sin, None, None, (kc, vc),
                cache_write_index, kv_valid, attn_impl, kind=kind,
            )
            return h2, ((kc2, vc2), None if cfg.is_hybrid else aux)

        cache = (kv_cache["k"], kv_cache["v"])
        with jax.named_scope("layer_scan"):
            h, ((ks, vs), aux) = _scan_layers(
                cfg, body, h,
                _zip_by_kind(cfg, layer_params, cache) if cfg.is_hybrid
                else (layer_params, cache))
    elif return_kv:
        def body(kind, h, lp):
            h2, kv, aux = _block(
                cfg, h, lp, cos, sin, segment_ids, positions,
                None, None, None, attn_impl, kind=kind,
            )
            return h2, (kv, None if cfg.is_hybrid else aux)

        with jax.named_scope("layer_scan"):
            h, ((ks, vs), aux) = _scan_layers(cfg, body, h, layer_params,
                                              remat)
    else:
        ks = vs = None
        from areal_tpu.parallel import pipeline as pp_mod

        mesh = current_mesh()
        n_micro = pp_mod.pick_pp_microbatches(
            mesh, cfg, h.shape[0], pp_microbatches, seq_len=h.shape[1]
        )
        if n_micro is not None:
            # Real pipeline parallelism: micro-batches stream through the
            # pp stages via collective permute (parallel/pipeline.py).
            h, aux = pp_mod.pipeline_apply_layers(
                cfg, layer_params, h, cos, sin, segment_ids, positions,
                mesh, n_micro, attn_impl=attn_impl, remat=remat,
            )
        else:
            # remat note: HBM-for-FLOPs trade (the reference relies on
            # Megatron activation checkpointing; here one jax.checkpoint
            # over the scan body).
            # Router jitter rides only this (training) path: decode and
            # KV-returning forwards are inference, where jitter is off by
            # construction; the pipeline path drops it rather than thread
            # keys through collective permutes.
            h, aux = apply_layer_stack(
                cfg, h, layer_params, cos, sin, segment_ids, positions,
                attn_impl=attn_impl, remat=remat, rng=rng,
            )
    # aux ys are stacked per-layer on a leading [n_layers] axis (already
    # reduced in the pipeline path). The optimized total SUMS over layers
    # (the reference's aux tracker accumulates every MoE layer's loss);
    # the diagnostic stats are reported as layer means — vector stats
    # (the [E] expert_load histogram) mean over the layer axis only.
    def over_layers(k, v):
        if k.startswith("dsa_"):  # an exact count every layer agrees on
            from areal_tpu.models.dsa import reduce_layers

            return reduce_layers(v)
        if k == "aux_total":
            return jnp.sum(v)
        return jnp.mean(v, axis=0) if v.ndim > 1 else jnp.mean(v)

    aux = ({k: over_layers(k, v) for k, v in aux.items()}
           if aux is not None else {})

    with jax.named_scope("final_norm"):
        if cfg.norm_type == "layer":
            h = layer_norm(
                h, params["final_ln"], params["final_ln_b"], cfg.rms_norm_eps
            )
        else:
            h = _rms(cfg, h, params["final_ln"])
    if return_hidden:
        out = h  # caller applies the head (e.g. chunked-logprob loss)
    else:
        out = apply_head(
            params, cfg, h, "logits" if not decode else "logits_decode"
        )
    kv_out = {"k": ks, "v": vs} if ks is not None else None
    if return_aux:
        return out, kv_out, aux
    return out, kv_out


def _zip_by_kind(cfg: TransformerConfig, layer_params: Params, per_layer):
    """``{kind: (that kind's stack, its layers' slices of per_layer)}``:
    the scan's ``xs`` where something stacked ``[n_layers, ...]`` in
    layer order (the K/V cache) rides beside a tree per kind."""
    out = {}
    for kind, lp in layer_params.items():
        idx = [i for i, k in enumerate(cfg.layer_kinds) if k == kind]
        if idx == list(range(idx[0], idx[-1] + 1)):
            idx = slice(idx[0], idx[-1] + 1)
        else:
            idx = jnp.asarray(idx)
        out[kind] = (lp, jax.tree.map(lambda a: a[idx], per_layer))
    return out


def kv_valid_by_kind(cfg: TransformerConfig, valid: jnp.ndarray,
                     distance: jnp.ndarray):
    """The cache slots each attention kind of the model may read: ``valid``
    (causal, written) for a full layer, and of those the slots less than
    the window behind the query (``distance`` = query position - slot
    position, broadcastable to ``valid``) for a sliding-window layer.
    A model whose layers are alike gets the one array."""
    by_kind = {
        kind: valid if cfg.window_of(kind) is None
        else valid & (distance < cfg.window_of(kind))
        for kind in dict.fromkeys(map(attention_kind, cfg.period_kinds))
    }
    if len(by_kind) == 1:
        (valid,) = by_kind.values()
        return valid
    return by_kind


def apply_head(params: Params, cfg: TransformerConfig, h, lg="logits"):
    """Final-hidden → logits (or values). Shared by forward and the
    engine's chunked-logprob path (backend/jax_train.py) so the head math
    has exactly one definition — ``cfg.logits_scaling`` included: every
    loss and logprob reads the logits here."""
    with jax.named_scope("head"):
        if cfg.is_critic:
            return (h @ params["value_head"])[..., 0]
        if cfg.logits_scaling != 1.0:
            # logits / logits_scaling, on the hidden width and not on the
            # vocabulary's (the product is linear in h)
            h = (h.astype(jnp.float32) / cfg.logits_scaling).astype(h.dtype)
        if cfg.tie_word_embeddings:
            return constrain(h @ params["embedding"].T, lg)
        return constrain(h @ params["lm_head"], lg)


def init_kv_cache(
    cfg: TransformerConfig, batch: int, length: int, dtype=jnp.float32
) -> Dict[str, jnp.ndarray]:
    if cfg.has_cacheless_layers:
        raise NotImplementedError(decode_refusal(cfg))
    shape = (cfg.n_layers, batch, length, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _mixer_param_counts(cfg: TransformerConfig) -> Dict[str, int]:
    """{mixer kind: parameters of one such layer, its norm included}."""
    from areal_tpu.models import moe as moemod

    d = cfg.hidden_dim
    counts = {ATTENTION_ONLY:
              d * (cfg.q_dim + 2 * cfg.kv_dim) + cfg.q_dim * d + d}
    for kind in cfg.layer_kinds:
        if kind not in MIXER_KINDS:
            counts[kind] = _block_param_count(cfg, has_dense_ffn(kind), kind)
    if cfg.ssm is not None and MAMBA in cfg.layer_kinds:
        counts[MAMBA] = _mamba_param_count(cfg) + d
    if cfg.moe is not None:
        counts[MOE_ONLY] = d + sum(
            math.prod(shape) for shape in moemod.moe_param_shapes(cfg).values())
    return counts


def _mamba_param_count(cfg: TransformerConfig) -> int:
    """Parameters of one Mamba-2 mixer, the norm in front not counted:
    the two projections, the convolution and its bias, dt_bias / A_log /
    D a head, the gated norm's weight."""
    ssm, d = cfg.ssm, cfg.hidden_dim
    return (d * ssm.in_proj_dim + (ssm.conv_kernel + 1) * ssm.conv_dim
            + 3 * ssm.n_heads + ssm.d_inner + ssm.d_inner * d)


def _block_param_count(cfg: TransformerConfig, dense_ffn: bool,
                       kind: str = FULL) -> int:
    """Parameters of one whole block (the biases of the qwen2 / gpt2
    families are not counted), whose FFN is the expert layer where the
    model has one, unless ``dense_ffn``; its mixer by ``kind``."""
    from areal_tpu.models import moe as moemod

    d, f = cfg.hidden_dim, cfg.intermediate_dim
    kind = attention_kind(kind)
    if kind == S6:
        s6 = cfg.s6
        attn = (d * 2 * s6.d_inner + s6.d_inner * (
            s6.conv_kernel + 1 + s6.x_proj_dim + s6.dt_rank + 1
            + s6.state_dim + 1 + d))
    elif kind == SSD:
        attn = _mamba_param_count(cfg)
    elif kind == GDN:
        from areal_tpu.models.gdn import gdn_param_count

        attn = gdn_param_count(cfg.gdn, d)
    elif kind == KDA:
        from areal_tpu.models.kda import kda_param_count

        attn = kda_param_count(cfg.kda, d)
    elif kind == CONV:
        from areal_tpu.models.shortconv import shortconv_param_count

        attn = shortconv_param_count(cfg.shortconv, d)
    elif kind == GMU:
        attn = 2 * d * cfg.s6.d_inner
    elif kind == CROSS:
        attn = 2 * d * cfg.q_dim
    elif cfg.mla is not None:
        from areal_tpu.models.mla import mla_param_count

        attn = mla_param_count(cfg.mla, d, cfg.n_q_heads)
    else:
        attn = d * (cfg.q_dim + 2 * cfg.kv_dim) + cfg.q_dim * d
    attends = kind not in ATTENTION_FREE_KINDS
    if cfg.differential_attention and attends:
        attn += 6 * cfg.head_dim
    if cfg.gated_attention and attends:
        attn += d * cfg.q_dim
    if cfg.dsa is not None and kind == FULL:
        from areal_tpu.models.dsa import indexer_param_count

        attn += indexer_param_count(cfg.dsa, d)
    norms = (4 if cfg.sandwich_norm else 2) * d
    if cfg.use_qk_norm and attends:
        norms += cfg.q_norm_dim + cfg.k_norm_dim
    if cfg.moe is not None and not dense_ffn:
        mlp = sum(math.prod(shape) for shape in
                  moemod.moe_param_shapes(cfg).values())
    elif cfg.mlp_type == "plain":
        mlp = 2 * d * f
    else:
        mlp = 3 * d * f
    return attn + mlp + norms


def param_count(cfg: TransformerConfig) -> int:
    n, d, v = cfg.n_layers, cfg.hidden_dim, cfg.vocab_size
    if cfg.is_hybrid:
        counts = _mixer_param_counts(cfg)
        head = 0 if cfg.tie_word_embeddings else d * v
        return v * d + d + head + sum(
            counts[kind] for kind in cfg.layer_kinds)
    per_layer = _block_param_count(cfg, dense_ffn=False)
    head = d * v if not (cfg.tie_word_embeddings or cfg.is_critic) else 0
    pos = (
        cfg.max_position_embeddings * d
        if cfg.pos_embedding == "learned"
        else 0
    )
    return v * d + n * per_layer + d + head + pos + (d if cfg.is_critic else 0)


def activated_param_count(cfg: TransformerConfig) -> int:
    """Parameters a token actually touches in one forward: for MoE, only
    ``top_k`` of the ``num_experts`` routed FFNs (plus router and shared
    expert) — the honest N for 6NT-style FLOPs/MFU accounting
    (base/monitor.py); equals :func:`param_count` for dense models."""
    if cfg.moe is None:
        return param_count(cfg)
    n, d, f = cfg.n_layers, cfg.hidden_dim, cfg.intermediate_dim
    fr = cfg.moe.routed_intermediate_dim or f
    if cfg.is_hybrid:  # the expert layers only, at the width they work in
        n, d = cfg.n_expert_layers, cfg.moe.latent_dim or d
    one = (3 if cfg.moe.gated_experts else 2) * d * fr
    total_mlp = cfg.moe.num_experts * one
    # on a share, the part of a token's top_k that is held here on average
    # (a fraction of an expert where few of many are held: 22 x 8 / 512)
    held_k = cfg.moe.top_k * cfg.moe.num_experts / cfg.moe.n_routed
    active_mlp = round(held_k * one)
    return param_count(cfg) - n * (total_mlp - active_mlp)
