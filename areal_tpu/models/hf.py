"""Bidirectional HF ↔ areal_tpu weight conversion + sharded safetensors IO.

Parity target: the reference's per-family converter registry
(``realhf/impl/model/conversion/hf_registry.py:32`` +
``realhf/api/from_hf/{llama,qwen2,qwen3,gemma,gpt2,mistral,mixtral}.py``).
Families covered: llama, qwen2 (qwen2.5), qwen3, mistral, gemma, gpt2,
mixtral, qwen3_moe, olmoe, mellum, nemotron_h, afmoe, phi4flash,
granitemoehybrid, qwen3_next, lfm2_moe, glm4_moe_lite, kimi_linear,
KeyeVL2.

Weights are stacked on a leading layer axis (see models/transformer.py), so
conversion transposes HF's ``[out, in]`` linear layout to ``[in, out]`` and
stacks per-layer tensors. Checkpoints are written as sharded safetensors
with an HF-style index (threaded writers, mirroring the reference's
``saveload_utils.py``) plus a genuine HF ``config.json`` so the output loads
directly in ``transformers.AutoModelForCausalLM``.
"""

from __future__ import annotations

import dataclasses
import json
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from areal_tpu.base import logging
from areal_tpu.models.config import (
    ATTENTION_ONLY,
    CONV,
    CROSS,
    DENSE_FFN,
    FULL,
    GDN,
    GMU,
    KDA,
    MAMBA,
    MOE_ONLY,
    S6,
    SLIDING,
    SPARSE_FFN,
    SSD,
    GDNConfig,
    KDAConfig,
    MLAConfig,
    MoEConfig,
    RopeConfig,
    S6Config,
    ShortConvConfig,
    SparseAttnConfig,
    SSMConfig,
    TransformerConfig,
    attention_kind,
)

logger = logging.getLogger("models.hf")

HF_FAMILIES: Dict[str, Callable] = {}


def register_hf_family(name: str):
    def deco(fn):
        HF_FAMILIES[name] = fn
        return fn

    return deco


def _base_kwargs(hf_config: Any) -> Dict[str, Any]:
    head_dim = getattr(hf_config, "head_dim", None) or (
        hf_config.hidden_size // hf_config.num_attention_heads
    )
    return dict(
        n_layers=hf_config.num_hidden_layers,
        hidden_dim=hf_config.hidden_size,
        n_q_heads=hf_config.num_attention_heads,
        n_kv_heads=getattr(hf_config, "num_key_value_heads", None)
        or hf_config.num_attention_heads,
        head_dim=head_dim,
        intermediate_dim=hf_config.intermediate_size,
        vocab_size=hf_config.vocab_size,
        rotary_base=getattr(hf_config, "rope_theta", 10000.0),
        rms_norm_eps=getattr(hf_config, "rms_norm_eps", 1e-6),
        tie_word_embeddings=getattr(hf_config, "tie_word_embeddings", False),
    )


def _llama_like(hf_config: Any) -> TransformerConfig:
    mt = getattr(hf_config, "model_type", "llama")
    return TransformerConfig(
        **_base_kwargs(hf_config),
        sliding_window=getattr(hf_config, "sliding_window", None)
        if getattr(hf_config, "use_sliding_window", True)
        else None,
        use_attention_bias=mt in ("qwen2",),
        use_qk_norm=mt in ("qwen3", "qwen3_moe"),
        hf_family=mt,
    )


for _fam in ("llama", "qwen2", "qwen3", "mistral"):
    register_hf_family(_fam)(_llama_like)


@register_hf_family("gemma")
def _gemma_config(hf_config: Any) -> TransformerConfig:
    act = getattr(hf_config, "hidden_activation", None) or "gelu_pytorch_tanh"
    return TransformerConfig(
        **_base_kwargs(hf_config),
        hidden_act="gelu_tanh" if "tanh" in act else "gelu",
        scale_embeddings=True,
        hf_family="gemma",
    )


@register_hf_family("gpt2")
def _gpt2_config(hf_config: Any) -> TransformerConfig:
    d = hf_config.n_embd
    return TransformerConfig(
        n_layers=hf_config.n_layer,
        hidden_dim=d,
        n_q_heads=hf_config.n_head,
        n_kv_heads=hf_config.n_head,
        head_dim=d // hf_config.n_head,
        intermediate_dim=hf_config.n_inner or 4 * d,
        vocab_size=hf_config.vocab_size,
        rms_norm_eps=hf_config.layer_norm_epsilon,
        tie_word_embeddings=True,
        use_attention_bias=True,
        use_attn_output_bias=True,
        hidden_act="gelu_tanh",  # gelu_new
        mlp_type="plain",
        norm_type="layer",
        pos_embedding="learned",
        max_position_embeddings=hf_config.n_positions,
        hf_family="gpt2",
    )


@register_hf_family("mixtral")
def _mixtral_config(hf_config: Any) -> TransformerConfig:
    return TransformerConfig(
        **_base_kwargs(hf_config),
        sliding_window=getattr(hf_config, "sliding_window", None),
        moe=MoEConfig(
            num_experts=hf_config.num_local_experts,
            top_k=hf_config.num_experts_per_tok,
            aux_loss_coeff=getattr(hf_config, "router_aux_loss_coef", 1e-3),
            norm_topk_prob=True,
        ),
        hf_family="mixtral",
    )


@register_hf_family("qwen3_moe")
def _qwen3_moe_config(hf_config: Any) -> TransformerConfig:
    return TransformerConfig(
        **_base_kwargs(hf_config),
        use_qk_norm=True,
        moe=MoEConfig(
            num_experts=hf_config.num_experts,
            top_k=hf_config.num_experts_per_tok,
            routed_intermediate_dim=hf_config.moe_intermediate_size,
            aux_loss_coeff=getattr(hf_config, "router_aux_loss_coef", 1e-3),
            norm_topk_prob=getattr(hf_config, "norm_topk_prob", True),
        ),
        hf_family="qwen3_moe",
    )


# KeyeVL2: keys of the family that no block here runs, by name: (key, the
# values that are run, why any other is refused).
KEYE_VL2_REFUSALS = (
    ("sliding_window", (None,), "sliding_window: a learned selection under "
     "a sliding window (the indexer would rank a window's keys) is not "
     "built"),
    ("use_sliding_window", (None, False), "use_sliding_window: see "
     "sliding_window"),
)
MROPE_REFUSAL = (
    "mrope_unequal_streams: the three position streams of mrope_section "
    "differ on a token (an image patch's temporal / height / width "
    "positions); without the vision tower every token's are equal and the "
    "rotation is the one-dimensional one, which is what runs")


def mrope_positions(position_ids) -> np.ndarray:
    """The ONE position stream of M-RoPE position ids ``[3, ..., T]``
    (temporal, height, width) where the three are equal on every token —
    text, which is all that runs without a vision tower; refused by name
    (``MROPE_REFUSAL``) where they differ."""
    ids = np.asarray(position_ids)
    if ids.ndim < 2 or ids.shape[0] != 3:
        raise ValueError(f"M-RoPE position ids are [3, ..., T]: {ids.shape}")
    if not (np.array_equal(ids[0], ids[1]) and np.array_equal(ids[0], ids[2])):
        raise NotImplementedError(MROPE_REFUSAL)
    return ids[0]


@register_hf_family("KeyeVL2")
def _keye_vl2_config(hf_config: Any) -> TransformerConfig:
    """Keye-VL-2.0 (Kwai-Keye, ``model_type`` KeyeVL2), the LANGUAGE MODEL
    (the vision tower is not built): qwen3_moe's blocks — GQA with a
    per-head q / k RMSNorm, RoPE at ``rope_theta``, an expert layer of
    ``num_experts`` softmax-routed experts of ``moe_intermediate_size`` in
    every block (top-k gates renormalised, no shared expert, no dropped
    token, no auxiliary loss unless ``router_aux_loss_coef`` says so) —
    with LEARNED SPARSE ATTENTION in every block (``sa_config``:
    ``indexer_num_heads`` x ``indexer_head_dim`` query heads over ONE key
    head, ``topk`` keys a query; ``q_chunk_size`` / ``kv_chunk_size``
    carried as the tiling they are read as: models/dsa.py).
    ``rope_scaling``'s ``mrope_section`` splits the rotary frequencies
    over three position streams that are equal on every token that is no
    image patch (:func:`mrope_positions`): the rotation that runs is the
    one-dimensional one; a ``rope_type`` other than ``default`` is
    refused. A SHARE holds ``num_experts`` of ``num_routed_experts``
    (:func:`_expert_share`)."""
    for key, run, why in KEYE_VL2_REFUSALS:
        if getattr(hf_config, key, None) not in run:
            raise NotImplementedError(why)
    scaling = getattr(hf_config, "rope_scaling", None) or {}
    kind = _get(scaling, "rope_type", _get(scaling, "type", "default"))
    if kind != "default":
        raise NotImplementedError(
            f"rope_scaling.rope_type {kind!r}: KeyeVL2 runs the plain "
            "table under mrope_section only")
    kw = _base_kwargs(hf_config)
    section = _get(scaling, "mrope_section")
    if section is not None and sum(section) != kw["head_dim"] // 2:
        raise ValueError(
            f"mrope_section {list(section)} does not split the "
            f"{kw['head_dim'] // 2} rotary frequencies of a head")
    sa = hf_config.sa_config
    if _get(sa, "indexer_num_kv_heads", 1) != 1:
        raise NotImplementedError(
            "sa_config.indexer_num_kv_heads != 1: the indexer's key is ONE "
            "head every query head of it reads")
    if set(getattr(hf_config, "mlp_only_layers", None) or ()) or getattr(
            hf_config, "decoder_sparse_step", 1) != 1:
        raise NotImplementedError(
            "mlp_only_layers / decoder_sparse_step: KeyeVL2 blocks that "
            "run a dense MLP are not read")
    held = hf_config.num_experts
    routed, first = _expert_share(hf_config, held)
    return TransformerConfig(
        **kw,
        use_qk_norm=True,
        max_position_embeddings=getattr(
            hf_config, "max_position_embeddings", None),
        dsa=SparseAttnConfig(
            n_heads=int(_get(sa, "indexer_num_heads")),
            head_dim=int(_get(sa, "indexer_head_dim")),
            top_k=int(_get(sa, "topk")),
            q_tile=int(_get(sa, "q_chunk_size", 512)),
            kv_tile=int(_get(sa, "kv_chunk_size", 512)),
        ),
        moe=MoEConfig(
            num_experts=held,
            top_k=hf_config.num_experts_per_tok,
            capacity_factor=None,
            routed_intermediate_dim=hf_config.moe_intermediate_size,
            aux_loss_coeff=float(
                getattr(hf_config, "router_aux_loss_coef", 0.0) or 0.0),
            norm_topk_prob=bool(getattr(hf_config, "norm_topk_prob", True)),
            router_experts=routed,
            first_expert=first,
        ),
        hf_family="KeyeVL2",
    )


@register_hf_family("olmoe")
def _olmoe_config(hf_config: Any) -> TransformerConfig:
    """OLMoE (``OlmoeForCausalLM``): every layer is an expert layer whose
    experts have ``intermediate_size`` (there is no dense MLP and no shared
    expert), q and k are RMS-normalised over the whole projected vector
    before the split into heads, the top-k gates are used as the softmax
    gave them unless ``norm_topk_prob``, and no token is ever dropped."""
    return TransformerConfig(
        **_base_kwargs(hf_config),
        use_qk_norm=True,
        qk_norm_extent="proj",
        max_position_embeddings=getattr(
            hf_config, "max_position_embeddings", None),
        moe=MoEConfig(
            num_experts=hf_config.num_experts,
            top_k=hf_config.num_experts_per_tok,
            capacity_factor=None,
            routed_intermediate_dim=hf_config.intermediate_size,
            aux_loss_coeff=getattr(hf_config, "router_aux_loss_coef", 0.01),
            norm_topk_prob=getattr(hf_config, "norm_topk_prob", False),
        ),
        hf_family="olmoe",
    )


# HF ``layer_types`` entry <-> attention kind.
_HF_LAYER_TYPES = {"full_attention": FULL, "sliding_attention": SLIDING}


def _get(block: Any, key: str, default=None):
    """A key of a nested config block: a dict (config.json) or an object."""
    if isinstance(block, dict):
        return block.get(key, default)
    return getattr(block, key, default)


def _rope_config(block: Any) -> RopeConfig:
    """One block of HF ``rope_parameters``."""
    if _get(block, "rope_type", "default") == "default":
        return RopeConfig(base=float(_get(block, "rope_theta")))
    if _get(block, "rope_type") != "yarn":
        raise NotImplementedError(
            f"rope_type {_get(block, 'rope_type')!r} (default and yarn are "
            "supported)")
    return RopeConfig(
        base=float(_get(block, "rope_theta")),
        factor=float(_get(block, "factor")),
        original_max_position=int(
            _get(block, "original_max_position_embeddings")),
        beta_fast=float(_get(block, "beta_fast", 32.0)),
        beta_slow=float(_get(block, "beta_slow", 1.0)),
        attention_factor=_get(block, "attention_factor"),
    )


@register_hf_family("mellum")
def _mellum_config(hf_config: Any) -> TransformerConfig:
    """Mellum 2 (JetBrains): qwen3_moe's expert layer (``num_experts`` of
    ``moe_intermediate_size``, top-k gates renormalised, no shared expert,
    no dropped token) under attention whose kind changes by layer —
    ``layer_types`` mixes sliding-window and full attention, and
    ``rope_parameters`` gives each kind its RoPE (plain on sliding layers,
    YaRN on full ones). No q/k norm, no biases.

    A SHARE of the model — one rank of the expert-parallel group that
    holds a layer — is a config whose ``num_experts`` is the experts held,
    with the scalar keys ``num_routed_experts`` (the published count, the
    router's width), ``expert_shard_count`` (the ranks sharing a layer)
    and ``expert_shard_index`` (this rank): it holds the experts from
    ``index * num_experts`` on."""
    kw = _base_kwargs(hf_config)
    ropes = getattr(hf_config, "rope_parameters", None) or {}
    layer_rope = tuple(
        (_HF_LAYER_TYPES[name], _rope_config(block))
        for name, block in sorted(
            (ropes if isinstance(ropes, dict) else vars(ropes)).items()))
    sliding = dict(layer_rope).get(SLIDING)
    if sliding is not None:
        kw["rotary_base"] = sliding.base
    types = getattr(hf_config, "layer_types", None)
    if types is not None:
        # a config cut in depth keeps the first layers' types
        types = tuple(_HF_LAYER_TYPES[t] for t in types)[:kw["n_layers"]]
        if len(types) != kw["n_layers"]:
            raise ValueError(
                f"{len(types)} layer_types for {kw['n_layers']} layers")
    ffn_types = (getattr(hf_config, "mlp_layer_types", None)
                 or ())[:kw["n_layers"]]
    if set(ffn_types) - {SPARSE_FFN}:
        raise NotImplementedError(
            f"mlp_layer_types {sorted(set(ffn_types))}: a mellum "
            f"checkpoint whose layers are not all {SPARSE_FFN!r} (dense "
            "blocks beside expert blocks are read for afmoe only)")
    held = hf_config.num_experts
    routed, first = _expert_share(hf_config, held)
    return TransformerConfig(
        **kw,
        sliding_window=getattr(hf_config, "sliding_window", None)
        if getattr(hf_config, "use_sliding_window", True) else None,
        layer_types=types,
        layer_rope=layer_rope or None,
        max_position_embeddings=getattr(
            hf_config, "max_position_embeddings", None),
        moe=MoEConfig(
            num_experts=held,
            top_k=hf_config.num_experts_per_tok,
            capacity_factor=None,
            routed_intermediate_dim=hf_config.moe_intermediate_size,
            aux_loss_coeff=getattr(hf_config, "router_aux_loss_coef", 1e-3),
            norm_topk_prob=getattr(hf_config, "norm_topk_prob", True),
            router_experts=routed,
            first_expert=first,
        ),
        hf_family="mellum",
    )


def _expert_share(hf_config: Any, held: int):
    """(router_experts, first_expert) of a SHARE of an expert layer — this
    repo's scalar keys ``num_routed_experts`` (the published count, the
    router's width), ``expert_shard_count`` (the ranks sharing a layer)
    and ``expert_shard_index`` (this rank, which holds the experts from
    ``index * held`` on); (None, 0) where every expert is held."""
    routed = getattr(hf_config, "num_routed_experts", None) or held
    shards = getattr(hf_config, "expert_shard_count", None) or routed // held
    if held * shards != routed:
        raise ValueError(
            f"{shards} shards of {held} experts are not the {routed} "
            "the router scores")
    first = held * int(getattr(hf_config, "expert_shard_index", 0) or 0)
    return (routed if routed != held else None), first


# HF ``hybrid_override_pattern`` letter <-> mixer kind.
_HYBRID_LETTERS = {"M": MAMBA, "E": MOE_ONLY, "*": ATTENTION_ONLY}


@register_hf_family("nemotron_h")
def _nemotron_h_config(hf_config: Any) -> TransformerConfig:
    """Nemotron-H / Nemotron 3 (``NemotronHForCausalLM``): every layer is
    ONE mixer, ``h + f(norm(h))``, its kind a letter of
    ``hybrid_override_pattern`` — ``M`` a Mamba-2 mixer (``d_inner =
    mamba_num_heads * mamba_head_dim``), ``E`` an expert layer (sigmoid
    scores, choice by score + ``e_score_correction_bias``, gates
    renormalised and scaled; ``relu2`` experts that are not gated, in a
    latent width where ``moe_latent_size`` is set; one shared expert on
    every token; no token dropped), ``*`` attention with no position
    embedding. A dense ``-`` (MLP) layer is not supported. A SHARE holds
    ``n_routed_experts`` of ``num_routed_experts`` (:func:`_expert_share`)."""
    pattern = hf_config.hybrid_override_pattern[:hf_config.num_hidden_layers]
    if len(pattern) != hf_config.num_hidden_layers or set(pattern) - set(
            _HYBRID_LETTERS):
        raise NotImplementedError(
            f"hybrid_override_pattern {pattern!r} for "
            f"{hf_config.num_hidden_layers} layers (letters "
            f"{sorted(_HYBRID_LETTERS)} are supported)")
    if getattr(hf_config, "n_group", 1) != 1:
        raise NotImplementedError("group-limited routing (n_group > 1)")
    act = getattr(hf_config, "mlp_hidden_act", "relu2")
    held = hf_config.n_routed_experts
    routed, first = _expert_share(hf_config, held)
    shared = (getattr(hf_config, "n_shared_experts", 0) or 0) * (
        getattr(hf_config, "moe_shared_expert_intermediate_size", 0) or 0)
    kw = _base_kwargs(hf_config)
    kw["rms_norm_eps"] = getattr(
        hf_config, "norm_eps", getattr(hf_config, "layer_norm_epsilon", 1e-5))
    return TransformerConfig(
        **kw,
        pos_embedding="none",
        layer_types=tuple(_HYBRID_LETTERS[c] for c in pattern),
        max_position_embeddings=getattr(
            hf_config, "max_position_embeddings", None),
        ssm=SSMConfig(
            n_heads=hf_config.mamba_num_heads,
            head_dim=hf_config.mamba_head_dim,
            n_groups=hf_config.n_groups,
            state_dim=hf_config.ssm_state_size,
            conv_kernel=hf_config.conv_kernel,
            chunk_size=hf_config.chunk_size,
            time_step_min=getattr(hf_config, "time_step_min", 0.001),
            time_step_max=getattr(hf_config, "time_step_max", 0.1),
            time_step_floor=getattr(hf_config, "time_step_floor", 1e-4),
        ),
        moe=MoEConfig(
            num_experts=held,
            top_k=hf_config.num_experts_per_tok,
            capacity_factor=None,
            routed_intermediate_dim=hf_config.moe_intermediate_size,
            shared_intermediate_dim=shared or None,
            aux_loss_coeff=0.0,
            norm_topk_prob=getattr(hf_config, "norm_topk_prob", True),
            router_experts=routed,
            first_expert=first,
            router_score="sigmoid",
            routed_scaling_factor=float(
                getattr(hf_config, "routed_scaling_factor", 1.0)),
            latent_dim=getattr(hf_config, "moe_latent_size", None),
            gated_experts=False,
            expert_act=act,
        ),
        hf_family="nemotron_h",
    )


@register_hf_family("afmoe")
def _afmoe_config(hf_config: Any) -> TransformerConfig:
    """Arcee Trinity (``AfmoeForCausalLM``): whole blocks of attention +
    FFN under sandwich norms (a second RMSNorm on each branch's output),
    the first ``num_dense_layers`` with a dense SwiGLU of
    ``intermediate_size``, the others with ``num_experts`` routed experts
    of ``moe_intermediate_size`` beside ``num_shared_experts`` shared ones
    (sigmoid scores, choice by score + ``expert_bias``, gates renormalised
    where ``route_norm`` and scaled by ``route_scale``, no token dropped);
    attention gated by ``sigmoid(x W_g)``, q/k RMS-normalised per head,
    its kind by ``layer_types`` (else full every
    ``global_attn_every_n_layers``-th layer), RoPE on the sliding layers
    and no position embedding on the full ones; the embedding times
    ``sqrt(hidden_size)`` where ``mup_enabled``. No auxiliary loss (the
    published ``load_balance_coeff`` is the pre-training recipe's). A
    SHARE holds ``num_experts`` of ``num_routed_experts``
    (:func:`_expert_share`)."""
    for key in ("n_group", "topk_group", "num_expert_groups",
                "num_limited_groups"):
        if getattr(hf_config, key, 1) not in (None, 1):
            raise NotImplementedError(
                f"group-limited routing ({key} = {getattr(hf_config, key)})")
    if getattr(hf_config, "score_func", "sigmoid") != "sigmoid":
        raise NotImplementedError(
            f"score_func {hf_config.score_func!r} (sigmoid is supported)")
    if getattr(hf_config, "rope_scaling", None) is not None:
        raise NotImplementedError(f"rope_scaling {hf_config.rope_scaling!r}")
    kw = _base_kwargs(hf_config)
    n = kw["n_layers"]
    types = getattr(hf_config, "layer_types", None)
    if types is None:
        every = hf_config.global_attn_every_n_layers
        types = ["full_attention" if (i + 1) % every == 0
                 else "sliding_attention" for i in range(n)]
    # a config cut in depth keeps the first layers' types
    types = tuple(_HF_LAYER_TYPES[t] for t in types)[:n]
    if len(types) != n:
        raise ValueError(f"{len(types)} layer_types for {n} layers")
    dense = int(getattr(hf_config, "num_dense_layers", 0) or 0)
    held = hf_config.num_experts
    routed, first = _expert_share(hf_config, held)
    shared = (getattr(hf_config, "num_shared_experts", 0) or 0
              ) * hf_config.moe_intermediate_size
    return TransformerConfig(
        **kw,
        sliding_window=getattr(hf_config, "sliding_window", None),
        layer_types=types,
        layer_rope=((FULL, None),),
        mlp_layer_types=tuple(
            DENSE_FFN if i < dense else SPARSE_FFN for i in range(n))
        if dense else None,
        use_qk_norm=True,
        gated_attention=True,
        sandwich_norm=True,
        scale_embeddings=bool(getattr(hf_config, "mup_enabled", False)),
        max_position_embeddings=getattr(
            hf_config, "max_position_embeddings", None),
        moe=MoEConfig(
            num_experts=held,
            top_k=hf_config.num_experts_per_tok,
            capacity_factor=None,
            routed_intermediate_dim=hf_config.moe_intermediate_size,
            shared_intermediate_dim=shared or None,
            aux_loss_coeff=0.0,
            norm_topk_prob=bool(getattr(hf_config, "route_norm", True)),
            router_experts=routed,
            first_expert=first,
            router_score="sigmoid",
            routed_scaling_factor=float(
                getattr(hf_config, "route_scale", 1.0)),
        ),
        hf_family="afmoe",
    )


# phi4flash: the letter of each layer kind in ``layer_pattern``.
_SAMBAY_LETTERS = {"M": S6, "S": SLIDING, "F": FULL, "G": GMU, "X": CROSS}


def sambay_pattern(n_layers: int, mb_per_layer: int) -> str:
    """The published layer pattern of a decoder-hybrid-decoder model of
    ``n_layers`` layers: a Mamba-1 block every ``mb_per_layer``-th layer
    of the self-decoder (up to and with layer n/2) and a gated memory
    unit in its place behind it; between them window attention, ONE full
    attention at layer n/2 + 1, and cross attention behind that."""
    half = n_layers // 2
    return "".join(
        ("M" if i <= half else "G") if i % mb_per_layer == 0
        else "S" if i < half else "F" if i == half + 1 else "X"
        for i in range(n_layers))


@register_hf_family("phi4flash")
def _phi4flash_config(hf_config: Any) -> TransformerConfig:
    """Phi-4-mini-flash (``Phi4FlashForCausalLM``; SambaY): whole blocks
    under LayerNorm with bias, no position embedding; the mixer by layer
    (:func:`sambay_pattern`, or this repo's key ``layer_pattern`` for a
    cut in depth that starts at the published layer ``first_layer_index``)
    a Mamba-1 selective scan (``d_inner = 2 hidden``, state 16, conv 4,
    ``dt_rank = ceil(hidden / 16)``: Mamba-1's defaults, which have no
    key), window or full differential attention, a gated memory unit over
    the last Mamba layer's scan output, or cross attention over the full
    layer's K/V; q/k/v/o carry biases."""
    kw = _base_kwargs(hf_config)
    n, d = kw["n_layers"], kw["hidden_dim"]
    # a config cut further in depth keeps the pattern's first layers
    pattern = (getattr(hf_config, "layer_pattern", None) or sambay_pattern(
        n, hf_config.mb_per_layer))[:n]
    if len(pattern) != n or set(pattern) - set(_SAMBAY_LETTERS):
        raise NotImplementedError(
            f"layer_pattern {pattern!r} for {n} layers (letters "
            f"{sorted(_SAMBAY_LETTERS)} are supported)")
    kw["rms_norm_eps"] = getattr(hf_config, "layer_norm_eps", 1e-5)
    return TransformerConfig(
        **kw,
        pos_embedding="none",
        norm_type="layer",
        hidden_act=getattr(hf_config, "hidden_act", "silu"),
        use_attention_bias=True,
        use_attn_output_bias=True,
        differential_attention=True,
        first_layer_index=int(getattr(hf_config, "first_layer_index", 0)),
        sliding_window=hf_config.sliding_window,
        layer_types=tuple(_SAMBAY_LETTERS[c] for c in pattern),
        max_position_embeddings=getattr(
            hf_config, "max_position_embeddings", None),
        s6=S6Config(d_inner=2 * d, state_dim=16, conv_kernel=4,
                    dt_rank=-(-d // 16)),
        hf_family="phi4flash",
    )


# granitemoehybrid: HF ``layer_types`` to the block kinds.
_GRANITE_LAYER_TYPES = {"mamba": SSD, "attention": FULL}
# Why the family's siblings with experts are not loaded, by name.
GRANITE_EXPERT_REFUSAL = (
    "expert_layers_beside_shared_mlp: num_local_experts > 0 puts a routed "
    "expert layer beside the shared MLP in every block, which no block "
    "here runs (only the family's dense models load)")


@register_hf_family("granitemoehybrid")
def _granitemoehybrid_config(hf_config: Any) -> TransformerConfig:
    """Granite 4.0-H (``GraniteMoeHybridForCausalLM``) with
    ``num_local_experts`` 0: whole blocks under RMSNorm, the mixer by
    ``layer_types`` — ``mamba`` a Mamba-2 mixer (``d_inner = mamba_n_heads
    * mamba_d_head``, ``mamba_n_groups`` B/C groups, a gated norm over
    each group's channels), ``attention`` GQA without bias — then the
    gated MLP ``shared_mlp`` of width ``shared_intermediate_size``; no
    position embedding where ``position_embedding_type`` is ``nope``; a
    tied head; and the family's four multipliers. A config cut in depth
    keeps ``num_hidden_layers`` entries of ``layer_types``, from this
    repo's key ``first_layer_index`` on (0: the first ones)."""
    if (getattr(hf_config, "num_local_experts", 0) or 0) > 0:
        raise NotImplementedError(GRANITE_EXPERT_REFUSAL)
    kw = _base_kwargs(hf_config)
    first = int(getattr(hf_config, "first_layer_index", 0))
    types = tuple(hf_config.layer_types)[first:first + kw["n_layers"]]
    if len(types) != kw["n_layers"] or set(types) - set(_GRANITE_LAYER_TYPES):
        raise NotImplementedError(
            f"layer_types {types!r} for {kw['n_layers']} layers "
            f"({sorted(_GRANITE_LAYER_TYPES)} are supported)")
    pos = getattr(hf_config, "position_embedding_type", "nope")
    if pos not in ("nope", "rope"):
        raise NotImplementedError(f"position_embedding_type {pos!r}")
    if getattr(hf_config, "normalization_function", "rmsnorm") != "rmsnorm":
        raise NotImplementedError(
            f"normalization_function {hf_config.normalization_function!r}")
    if getattr(hf_config, "mamba_proj_bias", False) or not getattr(
            hf_config, "mamba_conv_bias", True):
        raise NotImplementedError(
            "a Mamba-2 mixer with projection biases, or a convolution "
            "without one")
    kw["intermediate_dim"] = hf_config.shared_intermediate_size
    return TransformerConfig(
        **kw,
        pos_embedding="none" if pos == "nope" else "rope",
        hidden_act=getattr(hf_config, "hidden_act", "silu"),
        use_attention_bias=bool(getattr(hf_config, "attention_bias", False)),
        layer_types=tuple(_GRANITE_LAYER_TYPES[t] for t in types),
        max_position_embeddings=getattr(
            hf_config, "max_position_embeddings", None),
        ssm=SSMConfig(
            n_heads=hf_config.mamba_n_heads,
            head_dim=hf_config.mamba_d_head,
            n_groups=hf_config.mamba_n_groups,
            state_dim=hf_config.mamba_d_state,
            conv_kernel=hf_config.mamba_d_conv,
            chunk_size=hf_config.mamba_chunk_size,
        ),
        embedding_multiplier=float(
            getattr(hf_config, "embedding_multiplier", 1.0)),
        residual_multiplier=float(
            getattr(hf_config, "residual_multiplier", 1.0)),
        attention_multiplier=float(hf_config.attention_multiplier)
        if getattr(hf_config, "attention_multiplier", None) else None,
        logits_scaling=float(getattr(hf_config, "logits_scaling", 1.0)),
        hf_family="granitemoehybrid",
    )


# qwen3_next: HF ``layer_types`` to the block kinds.
_QWEN3_NEXT_LAYER_TYPES = {"linear_attention": GDN, "full_attention": FULL}
# Keys of the family that no block here runs, by name: (key, the value
# that is run, why any other is refused).
QWEN3_NEXT_REFUSALS = (
    ("mlp_only_layers", [], "dense_mlp_blocks: blocks whose FFN is a dense "
     "MLP beside expert blocks (mlp_only_layers) are not read for this "
     "family"),
    ("decoder_sparse_step", 1, "dense_mlp_blocks: an expert layer every "
     "decoder_sparse_step-th block only, dense MLPs between"),
    ("rope_scaling", None, "rope_scaling: a scaled RoPE under partial "
     "rotary"),
    ("attention_bias", False, "attention_bias: biases on q/k/v/o"),
    ("use_sliding_window", False, "use_sliding_window: a window on the "
     "full-attention blocks"),
    ("hidden_act", "silu", "hidden_act: experts that are not SwiGLU"),
)


@register_hf_family("qwen3_next")
def _qwen3_next_config(hf_config: Any) -> TransformerConfig:
    """Qwen3-Next (``Qwen3NextForCausalLM``): whole blocks under
    zero-centred RMSNorms (``x̂ (1 + w)``), the mixer by ``layer_types``
    (else full attention every ``full_attention_interval``-th block) —
    ``linear_attention`` a Gated DeltaNet mixer (models/gdn.py),
    ``full_attention`` GQA gated by ``sigmoid`` of a second half of
    ``q_proj``, q/k normed a head, RoPE on the first
    ``partial_rotary_factor`` of each head's dims — then in EVERY block
    an expert layer: softmax scores, the top ``num_experts_per_tok``
    renormalised, no token dropped, beside one shared expert scaled a
    token by ``sigmoid(x · shared_expert_gate)``. The multi-token-
    prediction head (``mtp.*``) is not read. A SHARE holds ``num_experts``
    of ``num_routed_experts`` (:func:`_expert_share`); a config cut in
    depth keeps ``num_hidden_layers`` layers from this repo's key
    ``first_layer_index`` on."""
    for key, run, why in QWEN3_NEXT_REFUSALS:
        if getattr(hf_config, key, None) not in (None, run):
            raise NotImplementedError(why)
    kw = _base_kwargs(hf_config)
    n = kw["n_layers"]
    first = int(getattr(hf_config, "first_layer_index", 0) or 0)
    types = getattr(hf_config, "layer_types", None)
    if types is None:
        every = int(getattr(hf_config, "full_attention_interval", 4))
        types = ["full_attention" if (i + 1) % every == 0
                 else "linear_attention" for i in range(first + n)]
    types = tuple(types)[first:first + n]
    if len(types) != n or set(types) - set(_QWEN3_NEXT_LAYER_TYPES):
        raise NotImplementedError(
            f"layer_types {types!r} for {n} layers "
            f"({sorted(_QWEN3_NEXT_LAYER_TYPES)} are supported)")
    held = hf_config.num_experts
    routed, first_expert = _expert_share(hf_config, held)
    shared = getattr(hf_config, "shared_expert_intermediate_size", None) or None
    return TransformerConfig(
        **kw,
        layer_types=tuple(_QWEN3_NEXT_LAYER_TYPES[t] for t in types),
        first_layer_index=first,
        use_qk_norm=True,
        gated_attention=True,
        zero_centered_norm=True,
        partial_rotary_factor=float(
            getattr(hf_config, "partial_rotary_factor", 1.0)),
        max_position_embeddings=getattr(
            hf_config, "max_position_embeddings", None),
        gdn=GDNConfig(
            n_k_heads=hf_config.linear_num_key_heads,
            n_v_heads=hf_config.linear_num_value_heads,
            k_head_dim=hf_config.linear_key_head_dim,
            v_head_dim=hf_config.linear_value_head_dim,
            conv_kernel=hf_config.linear_conv_kernel_dim,
        ),
        moe=MoEConfig(
            num_experts=held,
            top_k=hf_config.num_experts_per_tok,
            capacity_factor=None,
            routed_intermediate_dim=hf_config.moe_intermediate_size,
            shared_intermediate_dim=shared,
            shared_expert_gate=shared is not None,
            aux_loss_coeff=getattr(hf_config, "router_aux_loss_coef", 1e-3),
            norm_topk_prob=getattr(hf_config, "norm_topk_prob", True),
            router_experts=routed,
            first_expert=first_expert,
        ),
        hf_family="qwen3_next",
    )


# lfm2_moe: HF ``layer_types`` to the block kinds.
_LFM2_LAYER_TYPES = {"conv": CONV, "full_attention": FULL}
# Keys of the family that no block here runs, by name: (key, the value
# that is run, why any other is refused).
LFM2_MOE_REFUSALS = (
    ("conv_bias", False, "conv_bias: a bias on the short convolution and "
     "its two projections"),
    ("use_expert_bias", True, "use_expert_bias: a sigmoid router without "
     "its choice bias"),
    ("rope_scaling", None, "rope_scaling: a scaled RoPE"),
)


@register_hf_family("lfm2_moe")
def _lfm2_moe_config(hf_config: Any) -> TransformerConfig:
    """LFM2-MoE (LiquidAI, ``Lfm2MoeForCausalLM``): whole blocks under
    plain RMSNorms at ``norm_eps``, the mixer by ``layer_types`` — ``conv``
    a doubly gated short convolution of ``conv_L_cache`` taps
    (models/shortconv.py), ``full_attention`` GQA with q and k normed a
    head before RoPE (``rope_parameters``), no bias — then the FFN: a
    dense SwiGLU of ``intermediate_size`` on the first ``num_dense_layers``
    blocks, on the others ``num_experts`` routed experts of
    ``moe_intermediate_size`` (sigmoid scores, the choice by score +
    ``expert_bias``, the chosen scores renormalised where
    ``norm_topk_prob``, times ``routed_scaling_factor``; no shared expert,
    no dropped token, no auxiliary loss). The head is the embedding's
    transpose unless ``tie_word_embeddings`` is false. A SHARE holds
    ``num_experts`` of ``num_routed_experts`` (:func:`_expert_share`); a
    config cut in depth keeps the first layers' types.
    ``expert_bias_init_std`` (this repo's key, not the publisher's) is
    what a fresh ``expert_bias`` is drawn at."""
    for key, run, why in LFM2_MOE_REFUSALS:
        if getattr(hf_config, key, None) not in (None, run):
            raise NotImplementedError(why)
    kw = _base_kwargs(hf_config)
    n = kw["n_layers"]
    types = tuple(getattr(hf_config, "layer_types"))[:n]
    if len(types) != n or set(types) - set(_LFM2_LAYER_TYPES):
        raise NotImplementedError(
            f"layer_types {types!r} for {n} layers "
            f"({sorted(_LFM2_LAYER_TYPES)} are supported)")
    rope = getattr(hf_config, "rope_parameters", None)
    if rope is not None:
        rope = _rope_config(rope)
        if rope.factor is not None:
            raise NotImplementedError("rope_scaling: a scaled RoPE")
        kw["rotary_base"] = rope.base
    kw["rms_norm_eps"] = getattr(hf_config, "norm_eps", 1e-5)
    kw["tie_word_embeddings"] = bool(
        getattr(hf_config, "tie_word_embeddings", True))
    dense = int(getattr(hf_config, "num_dense_layers", 0) or 0)
    held = hf_config.num_experts
    routed, first = _expert_share(hf_config, held)
    return TransformerConfig(
        **kw,
        layer_types=tuple(_LFM2_LAYER_TYPES[t] for t in types),
        mlp_layer_types=tuple(
            DENSE_FFN if i < dense else SPARSE_FFN for i in range(n))
        if dense else None,
        use_qk_norm=True,
        max_position_embeddings=getattr(
            hf_config, "max_position_embeddings", None),
        shortconv=ShortConvConfig(kernel=int(hf_config.conv_L_cache)),
        moe=MoEConfig(
            num_experts=held,
            top_k=hf_config.num_experts_per_tok,
            capacity_factor=None,
            routed_intermediate_dim=hf_config.moe_intermediate_size,
            aux_loss_coeff=0.0,
            norm_topk_prob=bool(getattr(hf_config, "norm_topk_prob", True)),
            router_experts=routed,
            first_expert=first,
            router_score="sigmoid",
            routed_scaling_factor=float(
                getattr(hf_config, "routed_scaling_factor", 1.0)),
            router_bias_init_std=float(
                getattr(hf_config, "expert_bias_init_std", 0.02)),
        ),
        hf_family="lfm2_moe",
    )


# glm4_moe_lite: keys of the family that no block here runs, by name: (key,
# the values that are run, why any other is refused).
GLM4_MOE_LITE_REFUSALS = (
    ("n_group", (None, 1), "n_group: group-limited routing"),
    ("topk_group", (None, 1), "topk_group: group-limited routing"),
    ("topk_method", (None, "noaux_tc"), "topk_method: a choice other than "
     "the top-k of sigmoid score + e_score_correction_bias"),
    ("rope_scaling", (None,), "rope_scaling: a scaled RoPE (YaRN's factor "
     "on the softmax scale of latent attention)"),
    ("attention_bias", (None, False), "attention_bias: a bias on latent "
     "attention's projections"),
    ("partial_rotary_factor", (None, 1, 1.0), "partial_rotary_factor: a "
     "rotary part narrower than qk_rope_head_dim"),
)


def _mla_config(hf_config: Any) -> MLAConfig:
    """Latent attention's five sizes from the keys deepseek_v3,
    glm4_moe_lite and kimi_linear share; ``q_lora_rank`` null = no query
    latent."""
    rank = getattr(hf_config, "q_lora_rank", None)
    return MLAConfig(
        q_lora_rank=None if rank is None else int(rank),
        kv_lora_rank=int(hf_config.kv_lora_rank),
        qk_nope_head_dim=int(hf_config.qk_nope_head_dim),
        qk_rope_head_dim=int(hf_config.qk_rope_head_dim),
        v_head_dim=int(hf_config.v_head_dim))


@register_hf_family("glm4_moe_lite")
def _glm4_moe_lite_config(hf_config: Any) -> TransformerConfig:
    """GLM-4.7-Flash (zai-org, ``Glm4MoeLiteForCausalLM``; deepseek_v3's
    attention and expert layer under GLM's names): whole pre-norm blocks
    under plain RMSNorms, EVERY block's attention multi-head latent
    attention (``q_lora_rank``, ``kv_lora_rank``, ``qk_nope_head_dim`` +
    ``qk_rope_head_dim`` a query / key head, ``v_head_dim`` a value head:
    models/mla.py), RoPE at ``rope_theta`` over the rotary part; a dense
    SwiGLU of ``intermediate_size`` on the first ``first_k_dense_replace``
    blocks, on the others ``n_routed_experts`` routed experts of
    ``moe_intermediate_size`` (sigmoid scores, the choice by score +
    ``e_score_correction_bias``, the chosen scores renormalised where
    ``norm_topk_prob``, times ``routed_scaling_factor``) beside
    ``n_shared_experts`` shared ones, ungated and unscaled; no token
    dropped, no auxiliary loss; an untied head. A SHARE holds
    ``n_routed_experts`` of ``num_routed_experts`` (:func:`_expert_share`).
    ``num_nextn_predict_layers`` (the multi-token-prediction modules
    behind the last block) is carried through and nothing of it is built:
    their weights are skipped on load by name. ``expert_bias_init_std``
    (this repo's key) is what a fresh choice bias is drawn at."""
    for key, run, why in GLM4_MOE_LITE_REFUSALS:
        if getattr(hf_config, key, None) not in run:
            raise NotImplementedError(why)
    mla = _mla_config(hf_config)
    kw = _base_kwargs(hf_config)
    heads = hf_config.num_attention_heads
    if kw["n_kv_heads"] != heads:
        raise NotImplementedError(
            "num_key_value_heads != num_attention_heads under latent "
            "attention (the up-projection makes a key a query head)")
    kw["head_dim"] = mla.qk_head_dim
    n = kw["n_layers"]
    dense = min(int(getattr(hf_config, "first_k_dense_replace", 0) or 0), n)
    held = hf_config.n_routed_experts
    routed, first = _expert_share(hf_config, held)
    shared = (getattr(hf_config, "n_shared_experts", 0) or 0
              ) * hf_config.moe_intermediate_size
    return TransformerConfig(
        **kw,
        mla=mla,
        mlp_layer_types=tuple(
            DENSE_FFN if i < dense else SPARSE_FFN for i in range(n))
        if dense else None,
        max_position_embeddings=getattr(
            hf_config, "max_position_embeddings", None),
        moe=MoEConfig(
            num_experts=held,
            top_k=hf_config.num_experts_per_tok,
            capacity_factor=None,
            routed_intermediate_dim=hf_config.moe_intermediate_size,
            shared_intermediate_dim=shared or None,
            aux_loss_coeff=0.0,
            norm_topk_prob=bool(getattr(hf_config, "norm_topk_prob", True)),
            router_experts=routed,
            first_expert=first,
            router_score="sigmoid",
            routed_scaling_factor=float(
                getattr(hf_config, "routed_scaling_factor", 1.0)),
            router_bias_init_std=float(
                getattr(hf_config, "expert_bias_init_std", 0.02)),
        ),
        n_nextn_predict_layers=int(
            getattr(hf_config, "num_nextn_predict_layers", 0) or 0),
        hf_family="glm4_moe_lite",
    )


# kimi_linear: keys of the family that no block here runs, by name: (key,
# the values that are run, why any other is refused).
KIMI_LINEAR_REFUSALS = (
    ("num_expert_group", (None, 1), "num_expert_group: group-limited "
     "routing"),
    ("topk_group", (None, 1), "topk_group: group-limited routing"),
    ("moe_router_activation_func", (None, "sigmoid"),
     "moe_router_activation_func: a router score other than the sigmoid"),
    ("moe_layer_freq", (None, 1), "moe_layer_freq: an expert layer every "
     "n-th block only"),
    ("mla_use_nope", (True,), "mla_use_nope false: a rotated latent "
     "attention in this family"),
    ("rope_scaling", (None,), "rope_scaling: a scaled RoPE"),
    ("hidden_act", (None, "silu"), "hidden_act: experts that are not SwiGLU"),
)


def _kimi_held_layers(hf_config: Any, n: int) -> Tuple[int, ...]:
    """The published (1-based) block numbers of the model's ``n`` layers:
    the first ``n`` of this repo's key ``held_layers``, else 1 .. n."""
    held = tuple(int(i) for i in (getattr(hf_config, "held_layers", None)
                                  or range(1, n + 1)))[:n]
    if len(held) != n or list(held) != sorted(set(held)) or held[0] < 1:
        raise ValueError(f"held_layers {held!r} for {n} layers")
    return held


@register_hf_family("kimi_linear")
def _kimi_linear_config(hf_config: Any) -> TransformerConfig:
    """Kimi-Linear (moonshotai, ``KimiLinearForCausalLM``): whole pre-norm
    blocks under plain RMSNorms. By ``linear_attn_config`` (1-based block
    numbers) a block mixes with Kimi Delta Attention (``kda_layers``:
    models/kda.py, ``num_heads`` heads of ``head_dim``, convolutions of
    ``short_conv_kernel_size`` taps) or with latent attention
    (``full_attn_layers``: models/mla.py WITHOUT a query latent —
    ``q_lora_rank`` null —, without any position embedding —
    ``mla_use_nope`` —, and with ``v_head_dim`` narrower than the key's
    ``qk_nope_head_dim + qk_rope_head_dim``); a dense SwiGLU of
    ``intermediate_size`` on the first ``first_k_dense_replace`` blocks, on
    the others ``num_experts`` routed experts of ``moe_intermediate_size``
    (sigmoid scores, the choice by score + ``e_score_correction_bias``,
    the chosen scores renormalised where ``moe_renormalize``, times
    ``routed_scaling_factor``) beside ``num_shared_experts`` shared ones,
    ungated and unscaled; an untied head. A SHARE holds ``num_experts`` of
    ``num_routed_experts`` (:func:`_expert_share`); a cut in depth holds
    the published blocks ``held_layers`` (this repo's key)."""
    for key, run, why in KIMI_LINEAR_REFUSALS:
        if getattr(hf_config, key, None) not in run:
            raise NotImplementedError(why)
    mla = _mla_config(hf_config)
    kw = _base_kwargs(hf_config)
    heads = hf_config.num_attention_heads
    if kw["n_kv_heads"] != heads:
        raise NotImplementedError(
            "num_key_value_heads != num_attention_heads under latent "
            "attention (the up-projection makes a key a query head)")
    kw["head_dim"] = mla.qk_head_dim
    n = kw["n_layers"]
    lin = hf_config.linear_attn_config
    kda_at, full_at = set(lin["kda_layers"]), set(lin["full_attn_layers"])
    held = _kimi_held_layers(hf_config, n)
    if any(i not in kda_at | full_at or i in kda_at & full_at for i in held):
        raise ValueError(
            f"blocks {held!r} are not each one of kda_layers / "
            "full_attn_layers")
    dense = int(getattr(hf_config, "first_k_dense_replace", 0) or 0)
    n_held = hf_config.num_experts
    routed, first = _expert_share(hf_config, n_held)
    shared = (getattr(hf_config, "num_shared_experts", 0) or 0
              ) * hf_config.moe_intermediate_size
    return TransformerConfig(
        **kw,
        mla=mla,
        pos_embedding="none",
        kda=KDAConfig(
            n_heads=int(lin["num_heads"]), head_dim=int(lin["head_dim"]),
            conv_kernel=int(lin.get("short_conv_kernel_size", 4))),
        layer_types=tuple(KDA if i in kda_at else FULL for i in held),
        mlp_layer_types=tuple(
            DENSE_FFN if i <= dense else SPARSE_FFN for i in held)
        if any(i <= dense for i in held) else None,
        held_layers=held if held != tuple(range(1, n + 1)) else None,
        max_position_embeddings=getattr(hf_config, "model_max_length", None),
        moe=MoEConfig(
            num_experts=n_held,
            top_k=hf_config.num_experts_per_token,
            capacity_factor=None,
            routed_intermediate_dim=hf_config.moe_intermediate_size,
            shared_intermediate_dim=shared or None,
            aux_loss_coeff=0.0,
            norm_topk_prob=bool(getattr(hf_config, "moe_renormalize", True)),
            router_experts=routed,
            first_expert=first,
            router_score="sigmoid",
            routed_scaling_factor=float(
                getattr(hf_config, "routed_scaling_factor", 1.0)),
            router_bias_init_std=float(
                getattr(hf_config, "expert_bias_init_std", 0.02)),
        ),
        n_nextn_predict_layers=int(
            getattr(hf_config, "num_nextn_predict_layers", 0) or 0),
        hf_family="kimi_linear",
    )


def config_from_hf(hf_config: Any) -> TransformerConfig:
    """Build a TransformerConfig from a transformers PretrainedConfig."""
    mt = getattr(hf_config, "model_type", "llama")
    if mt not in HF_FAMILIES:
        raise NotImplementedError(f"unsupported HF model family: {mt}")
    return HF_FAMILIES[mt](hf_config)


def _np(t) -> np.ndarray:
    if hasattr(t, "detach"):  # torch tensor
        return t.detach().to("cpu").float().numpy()
    return np.asarray(t)


# ---------------- family weight codecs ----------------
#
# Each codec maps between an HF state dict (flat names, [out, in] linears)
# and the stacked areal_tpu pytree. The llama-style codec covers every
# family except gpt2 (fused c_attn + Conv1D layout).


def _llama_mapping(cfg: TransformerConfig) -> List[tuple]:
    """(pytree key, HF name fmt, transpose) for per-layer 2-D/1-D weights."""
    m = [
        ("ln1", "model.layers.{i}.input_layernorm.weight", False),
        ("ln2", "model.layers.{i}.post_attention_layernorm.weight", False),
        ("wq", "model.layers.{i}.self_attn.q_proj.weight", True),
        ("wk", "model.layers.{i}.self_attn.k_proj.weight", True),
        ("wv", "model.layers.{i}.self_attn.v_proj.weight", True),
        ("wo", "model.layers.{i}.self_attn.o_proj.weight", True),
    ]
    if cfg.moe is None:
        m += [
            ("w_gate", "model.layers.{i}.mlp.gate_proj.weight", True),
            ("w_up", "model.layers.{i}.mlp.up_proj.weight", True),
            ("w_down", "model.layers.{i}.mlp.down_proj.weight", True),
        ]
    if cfg.use_attention_bias:
        m += [
            ("bq", "model.layers.{i}.self_attn.q_proj.bias", False),
            ("bk", "model.layers.{i}.self_attn.k_proj.bias", False),
            ("bv", "model.layers.{i}.self_attn.v_proj.bias", False),
        ]
    if cfg.use_qk_norm:
        m += [
            ("q_norm", "model.layers.{i}.self_attn.q_norm.weight", False),
            ("k_norm", "model.layers.{i}.self_attn.k_norm.weight", False),
        ]
    return m


# A learned selection's indexer (models/dsa.py), under the block's
# ``indexer`` subtree: (leaf, HF name, transpose). ASSUMED names (the
# publisher's modelling code is not on this machine): DeepSeek-V3.2's
# ``self_attn.indexer.*`` with a full-rank ``wq``.
_INDEXER_NAMES = [
    ("wq", "model.layers.{i}.self_attn.indexer.wq.weight", True),
    ("wk", "model.layers.{i}.self_attn.indexer.wk.weight", True),
    ("ww", "model.layers.{i}.self_attn.indexer.weights_proj.weight", True),
    ("k_norm", "model.layers.{i}.self_attn.indexer.k_norm.weight", False),
    ("k_norm_b", "model.layers.{i}.self_attn.indexer.k_norm.bias", False),
]


def _moe_names(cfg: TransformerConfig) -> Dict[str, str]:
    if cfg.hf_family == "mixtral":
        return {
            "router": "model.layers.{i}.block_sparse_moe.gate.weight",
            "e_gate": "model.layers.{i}.block_sparse_moe.experts.{e}.w1.weight",
            "e_up": "model.layers.{i}.block_sparse_moe.experts.{e}.w3.weight",
            "e_down": "model.layers.{i}.block_sparse_moe.experts.{e}.w2.weight",
        }
    # qwen3_moe / olmoe / mellum layout
    return {
        "router": "model.layers.{i}.mlp.gate.weight",
        "e_gate": "model.layers.{i}.mlp.experts.{e}.gate_proj.weight",
        "e_up": "model.layers.{i}.mlp.experts.{e}.up_proj.weight",
        "e_down": "model.layers.{i}.mlp.experts.{e}.down_proj.weight",
    }


def _llama_from_sd(
    sd: Dict[str, Any], cfg: TransformerConfig, dtype: str
) -> Dict[str, Any]:
    def get(name):
        if name in sd:
            return _np(sd[name])
        raise KeyError(f"missing HF weight {name}; have e.g. {list(sd)[:5]}")

    def stack(fmt, transpose=True):
        ws = []
        for i in range(cfg.n_layers):
            w = _np(sd[fmt.format(i=i)])
            ws.append(w.T if transpose and w.ndim == 2 else w)
        return np.stack(ws).astype(dtype)

    layers: Dict[str, np.ndarray] = {}
    for key, fmt, tr in _llama_mapping(cfg):
        layers[key] = stack(fmt, transpose=tr)
    if cfg.dsa is not None:
        layers["indexer"] = {key: stack(fmt, transpose=tr)
                             for key, fmt, tr in _INDEXER_NAMES}
    if cfg.moe is not None:
        names = _moe_names(cfg)
        E = cfg.moe.num_experts
        layers["router"] = stack(names["router"])  # [n, D, E]
        for key in ("e_gate", "e_up", "e_down"):
            per_layer = []
            for i in range(cfg.n_layers):
                per_layer.append(np.stack([
                    _np(sd[names[key].format(i=i, e=e)]).T for e in range(E)
                ]))
            layers[key] = np.stack(per_layer).astype(dtype)  # [n, E, ., .]
    if cfg.scale_embeddings:  # gemma stores norm weights as (w − 1)
        for k in ("ln1", "ln2"):
            layers[k] = (layers[k] + 1.0).astype(dtype)

    params: Dict[str, Any] = {
        "embedding": get("model.embed_tokens.weight").astype(dtype),
        "layers": layers,
        "final_ln": get("model.norm.weight").astype(dtype),
    }
    if cfg.scale_embeddings:
        params["final_ln"] = (params["final_ln"] + 1.0).astype(dtype)
    if cfg.is_critic:
        if "score.weight" in sd:
            params["value_head"] = get("score.weight").T.astype(dtype)
        else:
            params["value_head"] = np.zeros((cfg.hidden_dim, 1), dtype)
    elif not cfg.tie_word_embeddings:
        params["lm_head"] = get("lm_head.weight").T.astype(dtype)
    return params


def _llama_to_sd(
    params: Dict[str, Any], cfg: TransformerConfig
) -> Dict[str, np.ndarray]:
    layers = {k: v if isinstance(v, dict) else np.asarray(v)
              for k, v in params["layers"].items()}
    if cfg.scale_embeddings:  # undo the gemma (w + 1) fold
        layers = dict(layers)
        for k in ("ln1", "ln2"):
            layers[k] = layers[k] - 1.0
    sd: Dict[str, np.ndarray] = {
        "model.embed_tokens.weight": np.asarray(params["embedding"]),
        "model.norm.weight": np.asarray(params["final_ln"])
        - (1.0 if cfg.scale_embeddings else 0.0),
    }
    for key, fmt, tr in _llama_mapping(cfg):
        w = layers[key]
        for i in range(cfg.n_layers):
            wi = w[i]
            sd[fmt.format(i=i)] = wi.T if tr and wi.ndim == 2 else wi
    if cfg.dsa is not None:
        for key, fmt, tr in _INDEXER_NAMES:
            w = np.asarray(layers["indexer"][key])
            for i in range(cfg.n_layers):
                sd[fmt.format(i=i)] = w[i].T if tr else w[i]
    if cfg.moe is not None:
        names = _moe_names(cfg)
        for i in range(cfg.n_layers):
            sd[names["router"].format(i=i)] = layers["router"][i].T
            for key in ("e_gate", "e_up", "e_down"):
                for e in range(cfg.moe.num_experts):
                    sd[names[key].format(i=i, e=e)] = layers[key][i, e].T
    if cfg.is_critic:
        sd["score.weight"] = np.asarray(params["value_head"]).T
    elif not cfg.tie_word_embeddings:
        sd["lm_head.weight"] = np.asarray(params["lm_head"]).T
    return sd


def _gpt2_from_sd(
    sd: Dict[str, Any], cfg: TransformerConfig, dtype: str
) -> Dict[str, Any]:
    """GPT-2: fused c_attn qkv, Conv1D layout ([in, out] — NO transpose),
    LayerNorm weights+biases, learned positions, 'transformer.' prefix
    (absent when loading from a bare GPT2Model state dict)."""
    pfx = "transformer." if any(k.startswith("transformer.") for k in sd) else ""

    def get(name):
        return _np(sd[pfx + name])

    d = cfg.hidden_dim
    n = cfg.n_layers

    def stack(fmt):
        return np.stack([_np(sd[pfx + fmt.format(i=i)]) for i in range(n)])

    c_attn_w = stack("h.{i}.attn.c_attn.weight")  # [n, d, 3d] Conv1D
    c_attn_b = stack("h.{i}.attn.c_attn.bias")  # [n, 3d]
    layers = {
        "ln1": stack("h.{i}.ln_1.weight").astype(dtype),
        "ln1_b": stack("h.{i}.ln_1.bias").astype(dtype),
        "ln2": stack("h.{i}.ln_2.weight").astype(dtype),
        "ln2_b": stack("h.{i}.ln_2.bias").astype(dtype),
        "wq": c_attn_w[:, :, :d].astype(dtype),
        "wk": c_attn_w[:, :, d : 2 * d].astype(dtype),
        "wv": c_attn_w[:, :, 2 * d :].astype(dtype),
        "bq": c_attn_b[:, :d].astype(dtype),
        "bk": c_attn_b[:, d : 2 * d].astype(dtype),
        "bv": c_attn_b[:, 2 * d :].astype(dtype),
        "wo": stack("h.{i}.attn.c_proj.weight").astype(dtype),
        "bo": stack("h.{i}.attn.c_proj.bias").astype(dtype),
        "w_up": stack("h.{i}.mlp.c_fc.weight").astype(dtype),
        "b_up": stack("h.{i}.mlp.c_fc.bias").astype(dtype),
        "w_down": stack("h.{i}.mlp.c_proj.weight").astype(dtype),
        "b_down": stack("h.{i}.mlp.c_proj.bias").astype(dtype),
    }
    return {
        "embedding": get("wte.weight").astype(dtype),
        "pos_embedding": get("wpe.weight").astype(dtype),
        "layers": layers,
        "final_ln": get("ln_f.weight").astype(dtype),
        "final_ln_b": get("ln_f.bias").astype(dtype),
    }


def _gpt2_to_sd(
    params: Dict[str, Any], cfg: TransformerConfig
) -> Dict[str, np.ndarray]:
    lp = {k: np.asarray(v) for k, v in params["layers"].items()}
    sd = {
        "transformer.wte.weight": np.asarray(params["embedding"]),
        "transformer.wpe.weight": np.asarray(params["pos_embedding"]),
        "transformer.ln_f.weight": np.asarray(params["final_ln"]),
        "transformer.ln_f.bias": np.asarray(params["final_ln_b"]),
    }
    for i in range(cfg.n_layers):
        p = f"transformer.h.{i}."
        sd[p + "ln_1.weight"] = lp["ln1"][i]
        sd[p + "ln_1.bias"] = lp["ln1_b"][i]
        sd[p + "ln_2.weight"] = lp["ln2"][i]
        sd[p + "ln_2.bias"] = lp["ln2_b"][i]
        sd[p + "attn.c_attn.weight"] = np.concatenate(
            [lp["wq"][i], lp["wk"][i], lp["wv"][i]], axis=1
        )
        sd[p + "attn.c_attn.bias"] = np.concatenate(
            [lp["bq"][i], lp["bk"][i], lp["bv"][i]]
        )
        sd[p + "attn.c_proj.weight"] = lp["wo"][i]
        sd[p + "attn.c_proj.bias"] = lp["bo"][i]
        sd[p + "mlp.c_fc.weight"] = lp["w_up"][i]
        sd[p + "mlp.c_fc.bias"] = lp["b_up"][i]
        sd[p + "mlp.c_proj.weight"] = lp["w_down"][i]
        sd[p + "mlp.c_proj.bias"] = lp["b_down"][i]
    return sd


# nemotron_h: (mixer kind, pytree key, HF name under
# ``backbone.layers.{i}.``, transpose). The depthwise convolution is
# ``[channels, 1, K]`` there and ``[K, channels]`` here.
_NEMOTRON_H_NAMES = [
    (MAMBA, "in_proj", "mixer.in_proj.weight", True),
    (MAMBA, "conv_b", "mixer.conv1d.bias", False),
    (MAMBA, "dt_bias", "mixer.dt_bias", False),
    (MAMBA, "A_log", "mixer.A_log", False),
    (MAMBA, "D", "mixer.D", False),
    (MAMBA, "norm", "mixer.norm.weight", False),
    (MAMBA, "out_proj", "mixer.out_proj.weight", True),
    (ATTENTION_ONLY, "wq", "mixer.q_proj.weight", True),
    (ATTENTION_ONLY, "wk", "mixer.k_proj.weight", True),
    (ATTENTION_ONLY, "wv", "mixer.v_proj.weight", True),
    (ATTENTION_ONLY, "wo", "mixer.o_proj.weight", True),
    (MOE_ONLY, "router", "mixer.gate.weight", True),
    (MOE_ONLY, "router_bias", "mixer.gate.e_score_correction_bias", False),
    (MOE_ONLY, "latent_down", "mixer.fc1_latent_proj.weight", True),
    (MOE_ONLY, "latent_up", "mixer.fc2_latent_proj.weight", True),
    (MOE_ONLY, "s_up", "mixer.shared_experts.up_proj.weight", True),
    (MOE_ONLY, "s_down", "mixer.shared_experts.down_proj.weight", True),
]
_NEMOTRON_H_EXPERTS = {"e_up": "mixer.experts.{e}.up_proj.weight",
                       "e_down": "mixer.experts.{e}.down_proj.weight"}


def _layers_in_order(params: Dict[str, Any], cfg: TransformerConfig):
    """(layer index, kind, that layer's leaves) in layer order — out of a
    tree per kind, or out of the one tree of a model whose layers share
    their shapes."""
    seen: Dict[str, int] = {}
    for i, kind in enumerate(cfg.layer_kinds):
        tree, j = params["layers"], i
        if cfg.is_hybrid:
            tree, j = tree[kind], seen.get(kind, 0)
            seen[kind] = j + 1
        yield i, kind, {k: np.asarray(v[j]) for k, v in tree.items()}


def _nemotron_h_to_sd(
    params: Dict[str, Any], cfg: TransformerConfig
) -> Dict[str, np.ndarray]:
    sd = {
        "backbone.embeddings.weight": np.asarray(params["embedding"]),
        "backbone.norm_f.weight": np.asarray(params["final_ln"]),
        "lm_head.weight": np.asarray(params["lm_head"]).T,
    }
    for i, kind, lp in _layers_in_order(params, cfg):
        pre = f"backbone.layers.{i}."
        sd[pre + "norm.weight"] = lp["ln"]
        for _, key, name, tr in (
                m for m in _NEMOTRON_H_NAMES if m[0] == kind and m[1] in lp):
            sd[pre + name] = lp[key].T if tr else lp[key]
        if kind == MAMBA:
            sd[pre + "mixer.conv1d.weight"] = lp["conv_w"].T[:, None, :]
        if kind == MOE_ONLY:
            for key, name in _NEMOTRON_H_EXPERTS.items():
                for e in range(cfg.moe.num_experts):
                    sd[pre + name.format(e=e)] = lp[key][e].T
    return sd


def _nemotron_h_from_sd(
    sd: Dict[str, Any], cfg: TransformerConfig, dtype: str
) -> Dict[str, Any]:
    per_kind: Dict[str, Dict[str, list]] = {}
    for i, kind in enumerate(cfg.layer_kinds):
        pre = f"backbone.layers.{i}."
        lp = per_kind.setdefault(kind, {})
        lp.setdefault("ln", []).append(_np(sd[pre + "norm.weight"]))
        for _, key, name, tr in (m for m in _NEMOTRON_H_NAMES
                                 if m[0] == kind and pre + m[2] in sd):
            w = _np(sd[pre + name])
            lp.setdefault(key, []).append(w.T if tr else w)
        if kind == MAMBA:
            lp.setdefault("conv_w", []).append(
                _np(sd[pre + "mixer.conv1d.weight"])[:, 0, :].T)
        if kind == MOE_ONLY:
            for key, name in _NEMOTRON_H_EXPERTS.items():
                lp.setdefault(key, []).append(np.stack([
                    _np(sd[pre + name.format(e=e)]).T
                    for e in range(cfg.moe.num_experts)]))
    return {
        "embedding": _np(sd["backbone.embeddings.weight"]).astype(dtype),
        "layers": {kind: {k: np.stack(v).astype(dtype)
                          for k, v in lp.items()}
                   for kind, lp in per_kind.items()},
        "final_ln": _np(sd["backbone.norm_f.weight"]).astype(dtype),
        "lm_head": _np(sd["lm_head.weight"]).T.astype(dtype),
    }


# afmoe: (pytree key, HF name under ``model.layers.{i}.``, transpose) of
# a block's leaves — a dense block has the ``mlp.*_proj`` three, an expert
# block the router, its bias, the shared expert and ``_AFMOE_EXPERTS``.
_AFMOE_NAMES = [
    ("ln1", "input_layernorm.weight", False),
    ("ln1_post", "post_attention_layernorm.weight", False),
    ("ln2", "pre_mlp_layernorm.weight", False),
    ("ln2_post", "post_mlp_layernorm.weight", False),
    ("wq", "self_attn.q_proj.weight", True),
    ("wk", "self_attn.k_proj.weight", True),
    ("wv", "self_attn.v_proj.weight", True),
    ("wo", "self_attn.o_proj.weight", True),
    ("wg", "self_attn.gate_proj.weight", True),
    ("q_norm", "self_attn.q_norm.weight", False),
    ("k_norm", "self_attn.k_norm.weight", False),
    ("w_gate", "mlp.gate_proj.weight", True),
    ("w_up", "mlp.up_proj.weight", True),
    ("w_down", "mlp.down_proj.weight", True),
    ("router", "mlp.router.gate.weight", True),
    ("router_bias", "mlp.expert_bias", False),
    ("s_gate", "mlp.shared_experts.gate_proj.weight", True),
    ("s_up", "mlp.shared_experts.up_proj.weight", True),
    ("s_down", "mlp.shared_experts.down_proj.weight", True),
]
_AFMOE_EXPERTS = {"e_gate": "mlp.experts.{e}.gate_proj.weight",
                  "e_up": "mlp.experts.{e}.up_proj.weight",
                  "e_down": "mlp.experts.{e}.down_proj.weight"}


def _afmoe_to_sd(
    params: Dict[str, Any], cfg: TransformerConfig, names=None,
) -> Dict[str, np.ndarray]:
    """``names``: another family's table of the same form whose experts'
    and top-level names are afmoe's (glm4_moe_lite: the blocks that are
    built, no ``model.layers.<n_layers>.*`` of a multi-token-prediction
    module)."""
    names = names or _AFMOE_NAMES
    sd = {
        "model.embed_tokens.weight": np.asarray(params["embedding"]),
        "model.norm.weight": np.asarray(params["final_ln"]),
        "lm_head.weight": np.asarray(params["lm_head"]).T,
    }
    for i, _, lp in _layers_in_order(params, cfg):
        pre = f"model.layers.{i}."
        for key, name, tr in names:
            if key in lp:
                sd[pre + name] = lp[key].T if tr else lp[key]
        for key, name in _AFMOE_EXPERTS.items():
            if key in lp:
                for e in range(cfg.moe.num_experts):
                    sd[pre + name.format(e=e)] = lp[key][e].T
    return sd


def _afmoe_from_sd(
    sd: Dict[str, Any], cfg: TransformerConfig, dtype: str, names=None,
) -> Dict[str, Any]:
    """Reads blocks 0 .. n_layers - 1 by ``names`` (as
    :func:`_afmoe_to_sd`): ``model.layers.<n>.*`` for n from ``n_layers``
    on (glm4_moe_lite's multi-token-prediction modules) are skipped by
    name, as ``transformers`` skips them."""
    names = names or _AFMOE_NAMES
    per_kind: Dict[str, Dict[str, list]] = {}
    for i, kind in enumerate(cfg.layer_kinds):
        pre = f"model.layers.{i}."
        lp = per_kind.setdefault(kind if cfg.is_hybrid else "", {})
        for key, name, tr in names:
            if pre + name in sd:
                w = _np(sd[pre + name])
                lp.setdefault(key, []).append(w.T if tr else w)
        for key, name in _AFMOE_EXPERTS.items():
            if pre + name.format(e=0) in sd:
                lp.setdefault(key, []).append(np.stack([
                    _np(sd[pre + name.format(e=e)]).T
                    for e in range(cfg.moe.num_experts)]))
    stacked = {kind: {k: np.stack(v).astype(dtype) for k, v in lp.items()}
               for kind, lp in per_kind.items()}
    return {
        "embedding": _np(sd["model.embed_tokens.weight"]).astype(dtype),
        "layers": stacked if cfg.is_hybrid else stacked[""],
        "final_ln": _np(sd["model.norm.weight"]).astype(dtype),
        "lm_head": _np(sd["lm_head.weight"]).T.astype(dtype),
    }


# phi4flash: (pytree key, HF name under ``model.layers.{i}.``, transpose).
# A block holds the leaves of its kind; ``Wqkv`` ([q | k | v] rows, q
# alone on a cross layer), the gated MLP's fused ``gate_up_proj`` and the
# depthwise convolution ``[channels, 1, K]`` are split and joined below.
_PHI4FLASH_NAMES = [
    ("ln1", "input_layernorm.weight", False),
    ("ln1_b", "input_layernorm.bias", False),
    ("ln2", "post_attention_layernorm.weight", False),
    ("ln2_b", "post_attention_layernorm.bias", False),
    ("w_down", "mlp.down_proj.weight", True),
    ("in_proj", "attn.in_proj.weight", True),
    ("conv_b", "attn.conv1d.bias", False),
    ("x_proj", "attn.x_proj.weight", True),
    ("dt_proj", "attn.dt_proj.weight", True),
    ("dt_bias", "attn.dt_proj.bias", False),
    ("A_log", "attn.A_log", False),
    ("D", "attn.D", False),
    ("out_proj", "attn.out_proj.weight", True),
    ("gmu_in", "attn.in_proj.weight", True),
    ("gmu_out", "attn.out_proj.weight", True),
    ("wo", "attn.out_proj.weight", True),
    ("bo", "attn.out_proj.bias", False),
    ("lambda_q1", "attn.inner_cross_attn.lambda_q1", False),
    ("lambda_k1", "attn.inner_cross_attn.lambda_k1", False),
    ("lambda_q2", "attn.inner_cross_attn.lambda_q2", False),
    ("lambda_k2", "attn.inner_cross_attn.lambda_k2", False),
    ("subln", "attn.inner_cross_attn.subln.weight", False),
]


def _phi4flash_to_sd(
    params: Dict[str, Any], cfg: TransformerConfig
) -> Dict[str, np.ndarray]:
    sd = {
        "model.embed_tokens.weight": np.asarray(params["embedding"]),
        "model.final_layernorm.weight": np.asarray(params["final_ln"]),
        "model.final_layernorm.bias": np.asarray(params["final_ln_b"]),
    }
    if not cfg.tie_word_embeddings:
        sd["lm_head.weight"] = np.asarray(params["lm_head"]).T
    for i, kind, lp in _layers_in_order(params, cfg):
        pre = f"model.layers.{i}."
        for key, name, tr in _PHI4FLASH_NAMES:
            if key in lp:
                sd[pre + name] = lp[key].T if tr else lp[key]
        sd[pre + "mlp.gate_up_proj.weight"] = np.concatenate(
            [lp["w_gate"].T, lp["w_up"].T])
        if kind == S6:
            sd[pre + "attn.conv1d.weight"] = lp["conv_w"].T[:, None, :]
        if "wq" in lp:
            qkv = [k for k in ("wq", "wk", "wv") if k in lp]
            sd[pre + "attn.Wqkv.weight"] = np.concatenate(
                [lp[k].T for k in qkv])
            sd[pre + "attn.Wqkv.bias"] = np.concatenate(
                [lp["b" + k[1]] for k in qkv])
    return sd


def _phi4flash_from_sd(
    sd: Dict[str, Any], cfg: TransformerConfig, dtype: str
) -> Dict[str, Any]:
    per_kind: Dict[str, Dict[str, list]] = {}
    qd, kvd, f = cfg.q_dim, cfg.kv_dim, cfg.intermediate_dim
    # in_proj / out_proj name an S6 mixer's, a memory unit's and
    # attention's matrices alike: a kind reads its own keys
    every = {"ln1", "ln1_b", "ln2", "ln2_b", "w_down"}
    mixer = {
        S6: {"in_proj", "conv_b", "x_proj", "dt_proj", "dt_bias", "A_log",
             "D", "out_proj"},
        GMU: {"gmu_in", "gmu_out"},
    }
    attention = {"wo", "bo", "lambda_q1", "lambda_k1", "lambda_q2",
                 "lambda_k2", "subln"}
    for i, kind in enumerate(cfg.layer_kinds):
        pre = f"model.layers.{i}."
        lp = per_kind.setdefault(kind, {})
        attends = kind not in (S6, GMU)
        keys = every | mixer.get(kind, attention)
        for key, name, tr in _PHI4FLASH_NAMES:
            if key in keys:
                w = _np(sd[pre + name])
                lp.setdefault(key, []).append(w.T if tr else w)
        gate_up = _np(sd[pre + "mlp.gate_up_proj.weight"])
        lp.setdefault("w_gate", []).append(gate_up[:f].T)
        lp.setdefault("w_up", []).append(gate_up[f:].T)
        if kind == S6:
            lp.setdefault("conv_w", []).append(
                _np(sd[pre + "attn.conv1d.weight"])[:, 0, :].T)
        if attends:
            w, b = _np(sd[pre + "attn.Wqkv.weight"]), _np(
                sd[pre + "attn.Wqkv.bias"])
            cuts = [("q", 0, qd)] + ([] if kind == CROSS else [
                ("k", qd, qd + kvd), ("v", qd + kvd, qd + 2 * kvd)])
            for c, lo, hi in cuts:
                lp.setdefault("w" + c, []).append(w[lo:hi].T)
                lp.setdefault("b" + c, []).append(b[lo:hi])
    out = {
        "embedding": _np(sd["model.embed_tokens.weight"]).astype(dtype),
        "layers": {kind: {k: np.stack(v).astype(dtype)
                          for k, v in lp.items()}
                   for kind, lp in per_kind.items()},
        "final_ln": _np(sd["model.final_layernorm.weight"]).astype(dtype),
        "final_ln_b": _np(sd["model.final_layernorm.bias"]).astype(dtype),
    }
    if not cfg.tie_word_embeddings:
        out["lm_head"] = _np(sd["lm_head.weight"]).T.astype(dtype)
    return out


# granitemoehybrid: (pytree key, HF name under ``model.layers.{i}.``,
# transpose). A block holds the leaves of its kind; the gated MLP's fused
# ``shared_mlp.input_linear`` ([gate | up] rows) and the depthwise
# convolution ``[channels, 1, K]`` are split and joined below.
_GRANITE_NAMES = [
    ("ln1", "input_layernorm.weight", False),
    ("ln2", "post_attention_layernorm.weight", False),
    ("w_down", "shared_mlp.output_linear.weight", True),
    ("in_proj", "mamba.in_proj.weight", True),
    ("conv_b", "mamba.conv1d.bias", False),
    ("dt_bias", "mamba.dt_bias", False),
    ("A_log", "mamba.A_log", False),
    ("D", "mamba.D", False),
    ("norm", "mamba.norm.weight", False),
    ("out_proj", "mamba.out_proj.weight", True),
    ("wq", "self_attn.q_proj.weight", True),
    ("wk", "self_attn.k_proj.weight", True),
    ("wv", "self_attn.v_proj.weight", True),
    ("wo", "self_attn.o_proj.weight", True),
    ("bq", "self_attn.q_proj.bias", False),
    ("bk", "self_attn.k_proj.bias", False),
    ("bv", "self_attn.v_proj.bias", False),
]


def _granitemoehybrid_to_sd(
    params: Dict[str, Any], cfg: TransformerConfig
) -> Dict[str, np.ndarray]:
    sd = {
        "model.embed_tokens.weight": np.asarray(params["embedding"]),
        "model.norm.weight": np.asarray(params["final_ln"]),
    }
    if not cfg.tie_word_embeddings:
        sd["lm_head.weight"] = np.asarray(params["lm_head"]).T
    for i, kind, lp in _layers_in_order(params, cfg):
        pre = f"model.layers.{i}."
        for key, name, tr in _GRANITE_NAMES:
            if key in lp:
                sd[pre + name] = lp[key].T if tr else lp[key]
        sd[pre + "shared_mlp.input_linear.weight"] = np.concatenate(
            [lp["w_gate"].T, lp["w_up"].T])
        if kind == SSD:
            sd[pre + "mamba.conv1d.weight"] = lp["conv_w"].T[:, None, :]
    return sd


def _granitemoehybrid_from_sd(
    sd: Dict[str, Any], cfg: TransformerConfig, dtype: str
) -> Dict[str, Any]:
    per_kind: Dict[str, Dict[str, list]] = {}
    f = cfg.intermediate_dim
    for i, kind in enumerate(cfg.layer_kinds):
        pre = f"model.layers.{i}."
        lp = per_kind.setdefault(kind, {})
        for key, name, tr in _GRANITE_NAMES:
            if pre + name in sd:
                w = _np(sd[pre + name])
                lp.setdefault(key, []).append(w.T if tr else w)
        gate_up = _np(sd[pre + "shared_mlp.input_linear.weight"])
        lp.setdefault("w_gate", []).append(gate_up[:f].T)
        lp.setdefault("w_up", []).append(gate_up[f:].T)
        if kind == SSD:
            lp.setdefault("conv_w", []).append(
                _np(sd[pre + "mamba.conv1d.weight"])[:, 0, :].T)
    out = {
        "embedding": _np(sd["model.embed_tokens.weight"]).astype(dtype),
        "layers": {kind: {k: np.stack(v).astype(dtype)
                          for k, v in lp.items()}
                   for kind, lp in per_kind.items()},
        "final_ln": _np(sd["model.norm.weight"]).astype(dtype),
    }
    if not cfg.tie_word_embeddings:
        out["lm_head"] = _np(sd["lm_head.weight"]).T.astype(dtype)
    return out


# qwen3_next: (pytree key, HF name under ``model.layers.{i}.``, transpose).
# A block holds the leaves of its kind. Three HF matrices are INTERLEAVED
# and are split and joined below: ``linear_attn.in_proj_qkvz`` and
# ``in_proj_ba`` by KEY head (:func:`_gdn_split` / :func:`_gdn_join`), and
# ``self_attn.q_proj`` by head, ``[q_h | gate_h]`` (``wq`` / ``wg``); the
# depthwise convolution is ``[channels, 1, K]``.
_QWEN3_NEXT_NAMES = [
    ("ln1", "input_layernorm.weight", False),
    ("ln2", "post_attention_layernorm.weight", False),
    ("gdn_dt_bias", "linear_attn.dt_bias", False),
    ("gdn_A_log", "linear_attn.A_log", False),
    ("gdn_norm", "linear_attn.norm.weight", False),
    ("gdn_out", "linear_attn.out_proj.weight", True),
    ("wk", "self_attn.k_proj.weight", True),
    ("wv", "self_attn.v_proj.weight", True),
    ("wo", "self_attn.o_proj.weight", True),
    ("q_norm", "self_attn.q_norm.weight", False),
    ("k_norm", "self_attn.k_norm.weight", False),
    ("router", "mlp.gate.weight", True),
    ("s_gate", "mlp.shared_expert.gate_proj.weight", True),
    ("s_up", "mlp.shared_expert.up_proj.weight", True),
    ("s_down", "mlp.shared_expert.down_proj.weight", True),
    ("s_sig", "mlp.shared_expert_gate.weight", True),
]


def _gdn_split(gdn: GDNConfig, qkvz: np.ndarray, ba: np.ndarray):
    """HF's ``in_proj_qkvz`` [2 G dk + 2 H dv, D] and ``in_proj_ba`` [2 H,
    D], whose rows go BY KEY HEAD j — ``[q_j | k_j | v of its r value
    heads | z of them]`` and ``[b of its r value heads | a of them]`` — to
    this repo's ``gdn_qkvz`` [D, q | k | v | z] and ``gdn_ba`` [D, b | a]."""
    G, dk = gdn.n_k_heads, gdn.k_head_dim
    rv = gdn.value_dim // G  # a key head's r value heads' channels
    r = gdn.n_v_heads // G
    D = qkvz.shape[-1]
    by_head = qkvz.reshape(G, 2 * dk + 2 * rv, D)
    parts = np.split(by_head, [dk, 2 * dk, 2 * dk + rv], axis=1)
    ba_head = ba.reshape(G, 2 * r, D)
    return (np.concatenate([p.reshape(-1, D) for p in parts]).T,
            np.concatenate([ba_head[:, :r].reshape(-1, D),
                            ba_head[:, r:].reshape(-1, D)]).T)


def _gdn_join(gdn: GDNConfig, qkvz: np.ndarray, ba: np.ndarray):
    """The inverse of :func:`_gdn_split`."""
    G, dk = gdn.n_k_heads, gdn.k_head_dim
    rv, r = gdn.value_dim // G, gdn.n_v_heads // G
    D = qkvz.shape[0]
    q, k, v, z = np.split(qkvz.T, [G * dk, 2 * G * dk,
                                   2 * G * dk + gdn.value_dim])
    b, a = np.split(ba.T, 2)
    return (np.concatenate([q.reshape(G, dk, D), k.reshape(G, dk, D),
                            v.reshape(G, rv, D), z.reshape(G, rv, D)],
                           axis=1).reshape(-1, D),
            np.concatenate([b.reshape(G, r, D), a.reshape(G, r, D)],
                           axis=1).reshape(-1, D))


def _qwen3_next_to_sd(
    params: Dict[str, Any], cfg: TransformerConfig
) -> Dict[str, np.ndarray]:
    sd = {
        "model.embed_tokens.weight": np.asarray(params["embedding"]),
        "model.norm.weight": np.asarray(params["final_ln"]),
        "lm_head.weight": np.asarray(params["lm_head"]).T,
    }
    H, dh = cfg.n_q_heads, cfg.head_dim
    for i, kind, lp in _layers_in_order(params, cfg):
        pre = f"model.layers.{i}."
        for key, name, tr in _QWEN3_NEXT_NAMES:
            if key in lp:
                sd[pre + name] = lp[key].T if tr else lp[key]
        for key, name in _AFMOE_EXPERTS.items():
            for e in range(cfg.moe.num_experts):
                sd[pre + name.format(e=e)] = lp[key][e].T
        if kind == GDN:
            qkvz, ba = _gdn_join(cfg.gdn, lp["gdn_qkvz"], lp["gdn_ba"])
            sd[pre + "linear_attn.in_proj_qkvz.weight"] = qkvz
            sd[pre + "linear_attn.in_proj_ba.weight"] = ba
            sd[pre + "linear_attn.conv1d.weight"] = lp["gdn_conv"].T[
                :, None, :]
        else:  # rows by head: [q_h | gate_h]
            D = lp["wq"].shape[0]
            sd[pre + "self_attn.q_proj.weight"] = np.concatenate(
                [lp["wq"].T.reshape(H, dh, D), lp["wg"].T.reshape(H, dh, D)],
                axis=1).reshape(-1, D)
    return sd


def _qwen3_next_from_sd(
    sd: Dict[str, Any], cfg: TransformerConfig, dtype: str
) -> Dict[str, Any]:
    mtp = sorted(k for k in sd if k.startswith("mtp."))
    if mtp:
        logger.warning("qwen3_next: %d multi-token-prediction tensors "
                       "(mtp.*) are not read", len(mtp))
    per_kind: Dict[str, Dict[str, list]] = {}
    H, dh = cfg.n_q_heads, cfg.head_dim
    for i, kind in enumerate(cfg.layer_kinds):
        pre = f"model.layers.{cfg.first_layer_index + i}."
        if pre + "input_layernorm.weight" not in sd:
            pre = f"model.layers.{i}."
        lp = per_kind.setdefault(kind, {})
        for key, name, tr in _QWEN3_NEXT_NAMES:
            if pre + name in sd:
                w = _np(sd[pre + name])
                lp.setdefault(key, []).append(w.T if tr else w)
        for key, name in _AFMOE_EXPERTS.items():
            lp.setdefault(key, []).append(np.stack([
                _np(sd[pre + name.format(e=e)]).T
                for e in range(cfg.moe.num_experts)]))
        if kind == GDN:
            qkvz, ba = _gdn_split(
                cfg.gdn, _np(sd[pre + "linear_attn.in_proj_qkvz.weight"]),
                _np(sd[pre + "linear_attn.in_proj_ba.weight"]))
            lp.setdefault("gdn_qkvz", []).append(qkvz)
            lp.setdefault("gdn_ba", []).append(ba)
            lp.setdefault("gdn_conv", []).append(
                _np(sd[pre + "linear_attn.conv1d.weight"])[:, 0, :].T)
        else:
            qg = _np(sd[pre + "self_attn.q_proj.weight"])
            qg = qg.reshape(H, 2, dh, qg.shape[-1])
            lp.setdefault("wq", []).append(qg[:, 0].reshape(H * dh, -1).T)
            lp.setdefault("wg", []).append(qg[:, 1].reshape(H * dh, -1).T)
    return {
        "embedding": _np(sd["model.embed_tokens.weight"]).astype(dtype),
        "layers": {kind: {k: np.stack(v).astype(dtype)
                          for k, v in lp.items()}
                   for kind, lp in per_kind.items()},
        "final_ln": _np(sd["model.norm.weight"]).astype(dtype),
        "lm_head": _np(sd["lm_head.weight"]).T.astype(dtype),
    }


# lfm2_moe: (pytree key, HF name under ``model.layers.{i}.``, transpose).
# A block holds the leaves of its kind; the depthwise convolution is
# ``[channels, 1, K]``, tap K - 1 on the token itself.
_LFM2_MOE_NAMES = [
    ("ln1", "operator_norm.weight", False),
    ("ln2", "ffn_norm.weight", False),
    ("sc_in", "conv.in_proj.weight", True),
    ("sc_out", "conv.out_proj.weight", True),
    ("wq", "self_attn.q_proj.weight", True),
    ("wk", "self_attn.k_proj.weight", True),
    ("wv", "self_attn.v_proj.weight", True),
    ("wo", "self_attn.out_proj.weight", True),
    ("q_norm", "self_attn.q_layernorm.weight", False),
    ("k_norm", "self_attn.k_layernorm.weight", False),
    ("w_gate", "feed_forward.w1.weight", True),
    ("w_up", "feed_forward.w3.weight", True),
    ("w_down", "feed_forward.w2.weight", True),
    ("router", "feed_forward.gate.weight", True),
    ("router_bias", "feed_forward.expert_bias", False),
]
_LFM2_MOE_EXPERTS = {"e_gate": "feed_forward.experts.{e}.w1.weight",
                     "e_up": "feed_forward.experts.{e}.w3.weight",
                     "e_down": "feed_forward.experts.{e}.w2.weight"}
_LFM2_CONV = "conv.conv.weight"


def _lfm2_moe_to_sd(
    params: Dict[str, Any], cfg: TransformerConfig
) -> Dict[str, np.ndarray]:
    sd = {
        "model.embed_tokens.weight": np.asarray(params["embedding"]),
        "model.embedding_norm.weight": np.asarray(params["final_ln"]),
    }
    if not cfg.tie_word_embeddings:
        sd["lm_head.weight"] = np.asarray(params["lm_head"]).T
    for i, _, lp in _layers_in_order(params, cfg):
        pre = f"model.layers.{i}."
        for key, name, tr in _LFM2_MOE_NAMES:
            if key in lp:
                sd[pre + name] = lp[key].T if tr else lp[key]
        if "sc_conv" in lp:
            sd[pre + _LFM2_CONV] = lp["sc_conv"].T[:, None, :]
        for key, name in _LFM2_MOE_EXPERTS.items():
            if key in lp:
                for e in range(cfg.moe.num_experts):
                    sd[pre + name.format(e=e)] = lp[key][e].T
    return sd


def _lfm2_moe_from_sd(
    sd: Dict[str, Any], cfg: TransformerConfig, dtype: str
) -> Dict[str, Any]:
    per_kind: Dict[str, Dict[str, list]] = {}
    for i, kind in enumerate(cfg.layer_kinds):
        pre = f"model.layers.{i}."
        lp = per_kind.setdefault(kind, {})
        for key, name, tr in _LFM2_MOE_NAMES:
            if pre + name in sd:
                w = _np(sd[pre + name])
                lp.setdefault(key, []).append(w.T if tr else w)
        if pre + _LFM2_CONV in sd:
            lp.setdefault("sc_conv", []).append(
                _np(sd[pre + _LFM2_CONV])[:, 0, :].T)
        for key, name in _LFM2_MOE_EXPERTS.items():
            if pre + name.format(e=0) in sd:
                lp.setdefault(key, []).append(np.stack([
                    _np(sd[pre + name.format(e=e)]).T
                    for e in range(cfg.moe.num_experts)]))
    params = {
        "embedding": _np(sd["model.embed_tokens.weight"]).astype(dtype),
        "layers": {kind: {k: np.stack(v).astype(dtype)
                          for k, v in lp.items()}
                   for kind, lp in per_kind.items()},
        "final_ln": _np(sd["model.embedding_norm.weight"]).astype(dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = _np(sd["lm_head.weight"]).T.astype(dtype)
    return params


# glm4_moe_lite: (pytree key, HF name under ``model.layers.{i}.``,
# transpose). ``kv_b_proj``'s rows are by head, ``[k_nope | v]`` a head —
# the layout ``wkv_b``'s columns have (models/mla.py), as ``q_b_proj``'s
# ``[q_nope | q_rope]`` a head is ``wq_b``'s. The experts' names and the
# codec are afmoe's (``_AFMOE_EXPERTS``, ``_afmoe_to_sd`` / ``_from_sd``).
_GLM4_MOE_LITE_NAMES = [
    ("ln1", "input_layernorm.weight", False),
    ("ln2", "post_attention_layernorm.weight", False),
    ("wq_a", "self_attn.q_a_proj.weight", True),
    ("q_a_norm", "self_attn.q_a_layernorm.weight", False),
    ("wq_b", "self_attn.q_b_proj.weight", True),
    ("wkv_a", "self_attn.kv_a_proj_with_mqa.weight", True),
    ("kv_a_norm", "self_attn.kv_a_layernorm.weight", False),
    ("wkv_b", "self_attn.kv_b_proj.weight", True),
    ("wo", "self_attn.o_proj.weight", True),
    ("w_gate", "mlp.gate_proj.weight", True),
    ("w_up", "mlp.up_proj.weight", True),
    ("w_down", "mlp.down_proj.weight", True),
    ("router", "mlp.gate.weight", True),
    ("router_bias", "mlp.gate.e_score_correction_bias", False),
    ("s_gate", "mlp.shared_experts.gate_proj.weight", True),
    ("s_up", "mlp.shared_experts.up_proj.weight", True),
    ("s_down", "mlp.shared_experts.down_proj.weight", True),
]


# kimi_linear: (pytree key, HF name under ``model.layers.{i}.``, transpose)
# — the names AS RECALLED (benchmark/configs/kimi-linear-48b-a3b.json,
# ``assumed``). A block holds the leaves of its kind; the KDA mixer's
# three projections, three depthwise convolutions (``[channels, 1, K]``)
# and three narrow projections are ONE matrix each here and are split and
# joined below; ``A_log`` is HF's ``[1, 1, H, 1]``.
_KIMI_LINEAR_NAMES = [
    ("ln1", "input_layernorm.weight", False),
    ("ln2", "post_attention_layernorm.weight", False),
    ("kda_f_b", "self_attn.f_b_proj.weight", True),
    ("kda_g_b", "self_attn.g_b_proj.weight", True),
    ("kda_dt_bias", "self_attn.dt_bias", False),
    ("kda_norm", "self_attn.o_norm.weight", False),
    ("kda_out", "self_attn.o_proj.weight", True),
    ("wq", "self_attn.q_proj.weight", True),
    ("wkv_a", "self_attn.kv_a_proj_with_mqa.weight", True),
    ("kv_a_norm", "self_attn.kv_a_layernorm.weight", False),
    ("wkv_b", "self_attn.kv_b_proj.weight", True),
    ("wo", "self_attn.o_proj.weight", True),
    ("w_gate", "mlp.gate_proj.weight", True),
    ("w_up", "mlp.up_proj.weight", True),
    ("w_down", "mlp.down_proj.weight", True),
    ("router", "block_sparse_moe.gate.weight", True),
    ("router_bias", "block_sparse_moe.gate.e_score_correction_bias", False),
    ("s_gate", "block_sparse_moe.shared_experts.gate_proj.weight", True),
    ("s_up", "block_sparse_moe.shared_experts.up_proj.weight", True),
    ("s_down", "block_sparse_moe.shared_experts.down_proj.weight", True),
]
_KIMI_LINEAR_EXPERTS = {"e_gate": "block_sparse_moe.experts.{e}.w1.weight",
                        "e_up": "block_sparse_moe.experts.{e}.w3.weight",
                        "e_down": "block_sparse_moe.experts.{e}.w2.weight"}
_KIMI_QKV = ("q", "k", "v")
_KIMI_GATES_A = ("b_proj", "f_a_proj", "g_a_proj")


def _kimi_layer_numbers(cfg: TransformerConfig) -> List[int]:
    """HF's 0-based ``model.layers`` index of each layer."""
    held = cfg.held_layers or range(1, cfg.n_layers + 1)
    return [i - 1 for i in held]


def _kimi_linear_to_sd(
    params: Dict[str, Any], cfg: TransformerConfig
) -> Dict[str, np.ndarray]:
    sd = {
        "model.embed_tokens.weight": np.asarray(params["embedding"]),
        "model.norm.weight": np.asarray(params["final_ln"]),
        "lm_head.weight": np.asarray(params["lm_head"]).T,
    }
    at = _kimi_layer_numbers(cfg)
    for i, kind, lp in _layers_in_order(params, cfg):
        pre = f"model.layers.{at[i]}."
        for key, name, tr in _KIMI_LINEAR_NAMES:
            if key in lp:
                sd[pre + name] = lp[key].T if tr else lp[key]
        for key, name in _KIMI_LINEAR_EXPERTS.items():
            if key in lp:
                for e in range(cfg.moe.num_experts):
                    sd[pre + name.format(e=e)] = lp[key][e].T
        if attention_kind(kind) != KDA:
            continue
        kda = cfg.kda
        for x, w, c in zip(_KIMI_QKV, np.split(lp["kda_qkv"], 3, axis=1),
                           np.split(lp["kda_conv"], 3, axis=1)):
            sd[pre + f"self_attn.{x}_proj.weight"] = w.T
            sd[pre + f"self_attn.{x}_conv1d.weight"] = c.T[:, None, :]
        for name, w in zip(_KIMI_GATES_A, np.split(
                lp["kda_gates_a"], [kda.n_heads, kda.n_heads + kda.gate_rank],
                axis=1)):
            sd[pre + f"self_attn.{name}.weight"] = w.T
        sd[pre + "self_attn.A_log"] = lp["kda_A_log"].reshape(1, 1, -1, 1)
    return sd


def _kimi_linear_from_sd(
    sd: Dict[str, Any], cfg: TransformerConfig, dtype: str
) -> Dict[str, Any]:
    per_kind: Dict[str, Dict[str, list]] = {}
    for kind, at in zip(cfg.layer_kinds, _kimi_layer_numbers(cfg)):
        pre = f"model.layers.{at}."
        lp = per_kind.setdefault(kind, {})
        is_kda = attention_kind(kind) == KDA
        for key, name, tr in _KIMI_LINEAR_NAMES:
            # ``self_attn.o_proj`` is a KDA block's ``kda_out`` and an
            # attention block's ``wo``
            if key.startswith("kda_") != is_kda and "self_attn." in name:
                continue
            if pre + name in sd:
                w = _np(sd[pre + name])
                lp.setdefault(key, []).append(w.T if tr else w)
        for key, name in _KIMI_LINEAR_EXPERTS.items():
            if pre + name.format(e=0) in sd:
                lp.setdefault(key, []).append(np.stack([
                    _np(sd[pre + name.format(e=e)]).T
                    for e in range(cfg.moe.num_experts)]))
        if not is_kda:
            continue
        lp.setdefault("kda_qkv", []).append(np.concatenate(
            [_np(sd[pre + f"self_attn.{x}_proj.weight"]).T
             for x in _KIMI_QKV], axis=1))
        lp.setdefault("kda_conv", []).append(np.concatenate(
            [_np(sd[pre + f"self_attn.{x}_conv1d.weight"])[:, 0, :].T
             for x in _KIMI_QKV], axis=1))
        lp.setdefault("kda_gates_a", []).append(np.concatenate(
            [_np(sd[pre + f"self_attn.{name}.weight"]).T
             for name in _KIMI_GATES_A], axis=1))
        lp.setdefault("kda_A_log", []).append(
            _np(sd[pre + "self_attn.A_log"]).reshape(-1))
    return {
        "embedding": _np(sd["model.embed_tokens.weight"]).astype(dtype),
        "layers": {kind: {k: np.stack(v).astype(dtype)
                          for k, v in lp.items()}
                   for kind, lp in per_kind.items()},
        "final_ln": _np(sd["model.norm.weight"]).astype(dtype),
        "lm_head": _np(sd["lm_head.weight"]).T.astype(dtype),
    }


def params_from_hf_state_dict(
    sd: Dict[str, Any], cfg: TransformerConfig, dtype: str = "float32"
) -> Dict[str, Any]:
    """HF causal-LM state dict → stacked areal_tpu param pytree (numpy)."""
    if cfg.hf_family == "gpt2":
        return _gpt2_from_sd(sd, cfg, dtype)
    if cfg.hf_family == "nemotron_h":
        return _nemotron_h_from_sd(sd, cfg, dtype)
    if cfg.hf_family == "afmoe":
        return _afmoe_from_sd(sd, cfg, dtype)
    if cfg.hf_family == "phi4flash":
        return _phi4flash_from_sd(sd, cfg, dtype)
    if cfg.hf_family == "granitemoehybrid":
        return _granitemoehybrid_from_sd(sd, cfg, dtype)
    if cfg.hf_family == "qwen3_next":
        return _qwen3_next_from_sd(sd, cfg, dtype)
    if cfg.hf_family == "lfm2_moe":
        return _lfm2_moe_from_sd(sd, cfg, dtype)
    if cfg.hf_family == "glm4_moe_lite":
        return _afmoe_from_sd(sd, cfg, dtype, _GLM4_MOE_LITE_NAMES)
    if cfg.hf_family == "kimi_linear":
        return _kimi_linear_from_sd(sd, cfg, dtype)
    return _llama_from_sd(sd, cfg, dtype)


def params_to_hf_state_dict(
    params: Dict[str, Any], cfg: TransformerConfig
) -> Dict[str, np.ndarray]:
    """Inverse conversion (for publishing weights / HF-format checkpoints)."""
    if cfg.hf_family == "gpt2":
        return _gpt2_to_sd(params, cfg)
    if cfg.hf_family == "nemotron_h":
        return _nemotron_h_to_sd(params, cfg)
    if cfg.hf_family == "afmoe":
        return _afmoe_to_sd(params, cfg)
    if cfg.hf_family == "phi4flash":
        return _phi4flash_to_sd(params, cfg)
    if cfg.hf_family == "granitemoehybrid":
        return _granitemoehybrid_to_sd(params, cfg)
    if cfg.hf_family == "qwen3_next":
        return _qwen3_next_to_sd(params, cfg)
    if cfg.hf_family == "lfm2_moe":
        return _lfm2_moe_to_sd(params, cfg)
    if cfg.hf_family == "glm4_moe_lite":
        return _afmoe_to_sd(params, cfg, _GLM4_MOE_LITE_NAMES)
    if cfg.hf_family == "kimi_linear":
        return _kimi_linear_to_sd(params, cfg)
    return _llama_to_sd(params, cfg)


# ---------------- HF config.json emission ----------------

_HF_ARCH = {
    "llama": "LlamaForCausalLM",
    "qwen2": "Qwen2ForCausalLM",
    "qwen3": "Qwen3ForCausalLM",
    "mistral": "MistralForCausalLM",
    "gemma": "GemmaForCausalLM",
    "gpt2": "GPT2LMHeadModel",
    "mixtral": "MixtralForCausalLM",
    "qwen3_moe": "Qwen3MoeForCausalLM",
    "olmoe": "OlmoeForCausalLM",
    "mellum": "MellumForCausalLM",
    "nemotron_h": "NemotronHForCausalLM",
    "afmoe": "AfmoeForCausalLM",
    "phi4flash": "Phi4FlashForCausalLM",
    "granitemoehybrid": "GraniteMoeHybridForCausalLM",
    "qwen3_next": "Qwen3NextForCausalLM",
    "lfm2_moe": "Lfm2MoeForCausalLM",
    "glm4_moe_lite": "Glm4MoeLiteForCausalLM",
    "kimi_linear": "KimiLinearForCausalLM",
    "KeyeVL2": "KeyeVL2ForConditionalGeneration",
}


def hf_config_dict(cfg: TransformerConfig) -> Dict[str, Any]:
    """A transformers-loadable config.json dict for ``cfg``'s family."""
    fam = cfg.hf_family or "llama"
    if fam == "gpt2":
        return {
            "model_type": "gpt2",
            "architectures": ["GPT2LMHeadModel"],
            "n_layer": cfg.n_layers,
            "n_embd": cfg.hidden_dim,
            "n_head": cfg.n_q_heads,
            "n_positions": cfg.max_position_embeddings,
            "n_ctx": cfg.max_position_embeddings,
            "n_inner": cfg.intermediate_dim,
            "vocab_size": cfg.vocab_size,
            "layer_norm_epsilon": cfg.rms_norm_eps,
            "activation_function": "gelu_new",
            "tie_word_embeddings": True,
        }
    if fam == "nemotron_h":
        return _nemotron_h_config_dict(cfg)
    if fam == "afmoe":
        return _afmoe_config_dict(cfg)
    if fam == "phi4flash":
        return _phi4flash_config_dict(cfg)
    if fam == "granitemoehybrid":
        return _granitemoehybrid_config_dict(cfg)
    if fam == "qwen3_next":
        return _qwen3_next_config_dict(cfg)
    if fam == "lfm2_moe":
        return _lfm2_moe_config_dict(cfg)
    if fam == "glm4_moe_lite":
        return _glm4_moe_lite_config_dict(cfg)
    if fam == "kimi_linear":
        return _kimi_linear_config_dict(cfg)
    d: Dict[str, Any] = {
        "model_type": fam,
        "architectures": [_HF_ARCH.get(fam, "LlamaForCausalLM")],
        "num_hidden_layers": cfg.n_layers,
        "hidden_size": cfg.hidden_dim,
        "num_attention_heads": cfg.n_q_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "head_dim": cfg.head_dim,
        "intermediate_size": cfg.intermediate_dim,
        "vocab_size": cfg.vocab_size,
        "rope_theta": cfg.rotary_base,
        "rms_norm_eps": cfg.rms_norm_eps,
        "tie_word_embeddings": cfg.tie_word_embeddings,
        "max_position_embeddings": cfg.max_position_embeddings or 32768,
        "hidden_act": "gelu_pytorch_tanh"
        if cfg.hidden_act == "gelu_tanh" and fam == "gemma"
        else ("silu" if cfg.hidden_act == "silu" else cfg.hidden_act),
        "torch_dtype": "float32",
    }
    if fam == "gemma":
        d["hidden_activation"] = "gelu_pytorch_tanh"
    if cfg.sliding_window is not None:
        d["sliding_window"] = cfg.sliding_window
    if cfg.moe is not None:
        if fam == "mixtral":
            d["num_local_experts"] = cfg.moe.num_experts
            d["num_experts_per_tok"] = cfg.moe.top_k
            d["router_aux_loss_coef"] = cfg.moe.aux_loss_coeff
        else:
            width = cfg.moe.routed_intermediate_dim or cfg.intermediate_dim
            d["num_experts"] = cfg.moe.num_experts
            d["num_experts_per_tok"] = cfg.moe.top_k
            d["norm_topk_prob"] = cfg.moe.norm_topk_prob
            d["router_aux_loss_coef"] = cfg.moe.aux_loss_coeff
            if fam == "olmoe":  # intermediate_size IS the expert width
                del d["head_dim"]
                d["intermediate_size"] = width
                d["attention_bias"] = False
                d["clip_qkv"] = None
            elif fam in ("mellum", "KeyeVL2"):
                d["moe_intermediate_size"] = width
                if fam == "mellum":
                    d["mlp_layer_types"] = ["sparse"] * cfg.n_layers
                else:
                    d["decoder_sparse_step"] = 1
                    d["mlp_only_layers"] = []
                if cfg.moe.is_share:
                    shards = cfg.moe.n_routed // cfg.moe.num_experts
                    d["num_routed_experts"] = cfg.moe.n_routed
                    d["expert_shard_count"] = shards
                    d["expert_shard_index"] = (
                        cfg.moe.first_expert // cfg.moe.num_experts)
            else:
                d["moe_intermediate_size"] = width
                d["decoder_sparse_step"] = 1
                d["mlp_only_layers"] = []
    if fam == "KeyeVL2":
        sa = cfg.dsa
        d["torch_dtype"] = "bfloat16"
        d["attention_bias"] = False
        d["use_sliding_window"] = False
        d["sliding_window"] = None
        half = cfg.head_dim // 2  # a quarter, then the rest in two
        first = half // 4
        d["rope_scaling"] = {
            "mrope_section": [first, (half - first) // 2,
                              half - first - (half - first) // 2],
            "rope_type": "default", "type": "default"}
        d["sa_config"] = {
            "indexer_head_dim": sa.head_dim, "indexer_num_heads": sa.n_heads,
            "indexer_num_kv_heads": 1, "kv_chunk_size": sa.kv_tile,
            "q_chunk_size": sa.q_tile, "topk": sa.top_k}
    if fam == "mellum":
        names = {kind: name for name, kind in _HF_LAYER_TYPES.items()}
        del d["rope_theta"]
        d["attention_bias"] = False
        d["use_sliding_window"] = cfg.sliding_window is not None
        d["layer_types"] = [names[k] for k in cfg.layer_kinds]
        d["rope_parameters"] = {
            names[kind]: _rope_dict(cfg.rope_of(kind))
            for kind in dict.fromkeys(cfg.layer_kinds)
        }
    return d


def _nemotron_h_config_dict(cfg: TransformerConfig) -> Dict[str, Any]:
    """The inverse of :func:`_nemotron_h_config`."""
    ssm, moe = cfg.ssm, cfg.moe
    letters = {kind: c for c, kind in _HYBRID_LETTERS.items()}
    d = {
        "model_type": "nemotron_h",
        "architectures": [_HF_ARCH["nemotron_h"]],
        "num_hidden_layers": cfg.n_layers,
        "hybrid_override_pattern": "".join(
            letters[k] for k in cfg.layer_kinds),
        "hidden_size": cfg.hidden_dim,
        "num_attention_heads": cfg.n_q_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "head_dim": cfg.head_dim,
        "attention_bias": False,
        "intermediate_size": cfg.intermediate_dim,
        "vocab_size": cfg.vocab_size,
        "norm_eps": cfg.rms_norm_eps,
        "layer_norm_epsilon": cfg.rms_norm_eps,
        "tie_word_embeddings": cfg.tie_word_embeddings,
        "max_position_embeddings": cfg.max_position_embeddings or 32768,
        "mamba_num_heads": ssm.n_heads,
        "mamba_head_dim": ssm.head_dim,
        "n_groups": ssm.n_groups,
        "ssm_state_size": ssm.state_dim,
        "conv_kernel": ssm.conv_kernel,
        "chunk_size": ssm.chunk_size,
        "expand": ssm.d_inner // cfg.hidden_dim or 1,
        "use_conv_bias": True,
        "mamba_proj_bias": False,
        "mamba_hidden_act": "silu",
        "time_step_min": ssm.time_step_min,
        "time_step_max": ssm.time_step_max,
        "time_step_floor": ssm.time_step_floor,
        "n_routed_experts": moe.num_experts,
        "num_experts_per_tok": moe.top_k,
        "moe_intermediate_size": moe.routed_intermediate_dim,
        "moe_shared_expert_intermediate_size":
            moe.shared_intermediate_dim or 0,
        "n_shared_experts": 1 if moe.shared_intermediate_dim else 0,
        "norm_topk_prob": moe.norm_topk_prob,
        "routed_scaling_factor": moe.routed_scaling_factor,
        "n_group": 1,
        "topk_group": 1,
        "mlp_hidden_act": moe.expert_act,
        "mlp_bias": False,
        "torch_dtype": "float32",
    }
    if moe.latent_dim:
        d["moe_latent_size"] = moe.latent_dim
    if moe.is_share:
        d["num_routed_experts"] = moe.n_routed
        d["expert_shard_count"] = moe.n_routed // moe.num_experts
        d["expert_shard_index"] = moe.first_expert // moe.num_experts
    return d


def _phi4flash_config_dict(cfg: TransformerConfig) -> Dict[str, Any]:
    """The inverse of :func:`_phi4flash_config`."""
    letters = {kind: c for c, kind in _SAMBAY_LETTERS.items()}
    pattern = "".join(letters[k] for k in cfg.layer_kinds)
    d = {
        "model_type": "phi4flash",
        "architectures": [_HF_ARCH["phi4flash"]],
        "num_hidden_layers": cfg.n_layers,
        "hidden_size": cfg.hidden_dim,
        "num_attention_heads": cfg.n_q_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "intermediate_size": cfg.intermediate_dim,
        "vocab_size": cfg.vocab_size,
        "layer_norm_eps": cfg.rms_norm_eps,
        "hidden_act": cfg.hidden_act,
        "sliding_window": cfg.sliding_window,
        "mb_per_layer": 2,
        "tie_word_embeddings": cfg.tie_word_embeddings,
        "mlp_bias": False,
        "lm_head_bias": False,
        "max_position_embeddings": cfg.max_position_embeddings or 262144,
        "torch_dtype": "float32",
    }
    if pattern != sambay_pattern(cfg.n_layers, 2) or cfg.first_layer_index:
        d["layer_pattern"] = pattern
        d["first_layer_index"] = cfg.first_layer_index
    return d


def _granitemoehybrid_config_dict(cfg: TransformerConfig) -> Dict[str, Any]:
    """The inverse of :func:`_granitemoehybrid_config`."""
    ssm = cfg.ssm
    names = {kind: t for t, kind in _GRANITE_LAYER_TYPES.items()}
    return {
        "model_type": "granitemoehybrid",
        "architectures": [_HF_ARCH["granitemoehybrid"]],
        "num_hidden_layers": cfg.n_layers,
        "layer_types": [names[k] for k in cfg.layer_kinds],
        "hidden_size": cfg.hidden_dim,
        "num_attention_heads": cfg.n_q_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "head_dim": cfg.head_dim,
        "attention_bias": cfg.use_attention_bias,
        "intermediate_size": cfg.intermediate_dim,
        "shared_intermediate_size": cfg.intermediate_dim,
        "num_local_experts": 0,
        "num_experts_per_tok": 0,
        "hidden_act": cfg.hidden_act,
        "vocab_size": cfg.vocab_size,
        "rms_norm_eps": cfg.rms_norm_eps,
        "normalization_function": "rmsnorm",
        "position_embedding_type":
            "nope" if cfg.pos_embedding == "none" else "rope",
        "rope_theta": cfg.rotary_base,
        "tie_word_embeddings": cfg.tie_word_embeddings,
        "max_position_embeddings": cfg.max_position_embeddings or 131072,
        "mamba_n_heads": ssm.n_heads,
        "mamba_d_head": ssm.head_dim,
        "mamba_n_groups": ssm.n_groups,
        "mamba_d_state": ssm.state_dim,
        "mamba_d_conv": ssm.conv_kernel,
        "mamba_chunk_size": ssm.chunk_size,
        "mamba_expand": ssm.d_inner // cfg.hidden_dim or 1,
        "mamba_conv_bias": True,
        "mamba_proj_bias": False,
        "embedding_multiplier": cfg.embedding_multiplier,
        "residual_multiplier": cfg.residual_multiplier,
        "attention_multiplier":
            cfg.attention_multiplier or cfg.head_dim ** -0.5,
        "logits_scaling": cfg.logits_scaling,
        "torch_dtype": "float32",
    }


def _qwen3_next_config_dict(cfg: TransformerConfig) -> Dict[str, Any]:
    """The inverse of :func:`_qwen3_next_config`."""
    gdn, moe = cfg.gdn, cfg.moe
    names = {kind: t for t, kind in _QWEN3_NEXT_LAYER_TYPES.items()}
    full = [i + 1 for i, k in enumerate(cfg.layer_kinds) if k == FULL]
    d = {
        "model_type": "qwen3_next",
        "architectures": [_HF_ARCH["qwen3_next"]],
        "num_hidden_layers": cfg.n_layers,
        "layer_types": [names[k] for k in cfg.layer_kinds],
        "full_attention_interval": full[0] if full else cfg.n_layers + 1,
        "hidden_size": cfg.hidden_dim,
        "num_attention_heads": cfg.n_q_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "head_dim": cfg.head_dim,
        "attention_bias": False,
        "partial_rotary_factor": cfg.partial_rotary_factor,
        "rope_theta": cfg.rotary_base,
        "rope_scaling": None,
        "intermediate_size": cfg.intermediate_dim,
        "hidden_act": "silu",
        "vocab_size": cfg.vocab_size,
        "rms_norm_eps": cfg.rms_norm_eps,
        "tie_word_embeddings": cfg.tie_word_embeddings,
        "max_position_embeddings": cfg.max_position_embeddings or 262144,
        "use_sliding_window": False,
        "linear_num_key_heads": gdn.n_k_heads,
        "linear_num_value_heads": gdn.n_v_heads,
        "linear_key_head_dim": gdn.k_head_dim,
        "linear_value_head_dim": gdn.v_head_dim,
        "linear_conv_kernel_dim": gdn.conv_kernel,
        "decoder_sparse_step": 1,
        "mlp_only_layers": [],
        "moe_intermediate_size": moe.routed_intermediate_dim,
        "shared_expert_intermediate_size": moe.shared_intermediate_dim,
        "num_experts": moe.num_experts,
        "num_experts_per_tok": moe.top_k,
        "norm_topk_prob": moe.norm_topk_prob,
        "router_aux_loss_coef": moe.aux_loss_coeff,
        "torch_dtype": "float32",
    }
    if cfg.first_layer_index:
        d["first_layer_index"] = cfg.first_layer_index
    if moe.is_share:
        d["num_routed_experts"] = moe.n_routed
        d["expert_shard_count"] = moe.n_routed // moe.num_experts
        d["expert_shard_index"] = moe.first_expert // moe.num_experts
    return d


def _lfm2_moe_config_dict(cfg: TransformerConfig) -> Dict[str, Any]:
    """The inverse of :func:`_lfm2_moe_config`."""
    moe = cfg.moe
    names = {kind: name for name, kind in _LFM2_LAYER_TYPES.items()}
    d = {
        "model_type": "lfm2_moe",
        "architectures": [_HF_ARCH["lfm2_moe"]],
        "num_hidden_layers": cfg.n_layers,
        "num_dense_layers": (cfg.mlp_layer_types or ()).count(DENSE_FFN),
        "layer_types": [names[attention_kind(k)] for k in cfg.layer_kinds],
        "hidden_size": cfg.hidden_dim,
        "num_attention_heads": cfg.n_q_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "intermediate_size": cfg.intermediate_dim,
        "moe_intermediate_size": moe.routed_intermediate_dim,
        "vocab_size": cfg.vocab_size,
        "norm_eps": cfg.rms_norm_eps,
        "rope_parameters": _rope_dict(RopeConfig(base=cfg.rotary_base)),
        "conv_L_cache": cfg.shortconv.kernel,
        "conv_bias": False,
        "tie_word_embeddings": cfg.tie_word_embeddings,
        "max_position_embeddings": cfg.max_position_embeddings or 128000,
        "num_experts": moe.num_experts,
        "num_experts_per_tok": moe.top_k,
        "use_expert_bias": True,
        "norm_topk_prob": moe.norm_topk_prob,
        "routed_scaling_factor": moe.routed_scaling_factor,
        "torch_dtype": "float32",
    }
    if moe.is_share:
        d["num_routed_experts"] = moe.n_routed
        d["expert_shard_count"] = moe.n_routed // moe.num_experts
        d["expert_shard_index"] = moe.first_expert // moe.num_experts
    if moe.router_bias_init_std != 0.02:
        d["expert_bias_init_std"] = moe.router_bias_init_std
    return d


def _glm4_moe_lite_config_dict(cfg: TransformerConfig) -> Dict[str, Any]:
    """The inverse of :func:`_glm4_moe_lite_config`."""
    moe, mla = cfg.moe, cfg.mla
    d = {
        "model_type": "glm4_moe_lite",
        "architectures": [_HF_ARCH["glm4_moe_lite"]],
        "num_hidden_layers": cfg.n_layers,
        "first_k_dense_replace": (cfg.mlp_layer_types or ()).count(DENSE_FFN),
        "hidden_size": cfg.hidden_dim,
        "num_attention_heads": cfg.n_q_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "q_lora_rank": mla.q_lora_rank,
        "kv_lora_rank": mla.kv_lora_rank,
        "qk_nope_head_dim": mla.qk_nope_head_dim,
        "qk_rope_head_dim": mla.qk_rope_head_dim,
        "v_head_dim": mla.v_head_dim,
        "intermediate_size": cfg.intermediate_dim,
        "moe_intermediate_size": moe.routed_intermediate_dim,
        "vocab_size": cfg.vocab_size,
        "hidden_act": "silu",
        "attention_bias": False,
        "rms_norm_eps": cfg.rms_norm_eps,
        "rope_theta": cfg.rotary_base,
        "rope_scaling": None,
        "partial_rotary_factor": 1,
        "tie_word_embeddings": cfg.tie_word_embeddings,
        "max_position_embeddings": cfg.max_position_embeddings or 202752,
        "n_routed_experts": moe.num_experts,
        "n_shared_experts": (moe.shared_intermediate_dim or 0)
        // moe.routed_intermediate_dim,
        "num_experts_per_tok": moe.top_k,
        "topk_method": "noaux_tc",
        "n_group": 1, "topk_group": 1,
        "norm_topk_prob": moe.norm_topk_prob,
        "routed_scaling_factor": moe.routed_scaling_factor,
        "num_nextn_predict_layers": cfg.n_nextn_predict_layers,
        "torch_dtype": "float32",
    }
    if moe.is_share:
        d["num_routed_experts"] = moe.n_routed
        d["expert_shard_count"] = moe.n_routed // moe.num_experts
        d["expert_shard_index"] = moe.first_expert // moe.num_experts
    if moe.router_bias_init_std != 0.02:
        d["expert_bias_init_std"] = moe.router_bias_init_std
    return d


def _kimi_linear_config_dict(cfg: TransformerConfig) -> Dict[str, Any]:
    """The inverse of :func:`_kimi_linear_config`. ``linear_attn_config``
    lists the blocks this model holds, by their published numbers."""
    moe, mla, kda = cfg.moe, cfg.mla, cfg.kda
    held = cfg.held_layers or tuple(range(1, cfg.n_layers + 1))
    kinds = [attention_kind(k) for k in cfg.layer_kinds]
    dense = [i for i, f in zip(held, cfg.mlp_layer_types or ())
             if f == DENSE_FFN]
    d = {
        "model_type": "kimi_linear",
        "architectures": [_HF_ARCH["kimi_linear"]],
        "num_hidden_layers": cfg.n_layers,
        "first_k_dense_replace": max(dense, default=0),
        "hidden_size": cfg.hidden_dim,
        "num_attention_heads": cfg.n_q_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "head_dim": cfg.hidden_dim // cfg.n_q_heads,
        "q_lora_rank": mla.q_lora_rank,
        "kv_lora_rank": mla.kv_lora_rank,
        "qk_nope_head_dim": mla.qk_nope_head_dim,
        "qk_rope_head_dim": mla.qk_rope_head_dim,
        "v_head_dim": mla.v_head_dim,
        "mla_use_nope": True,
        "linear_attn_config": {
            "kda_layers": [i for i, k in zip(held, kinds) if k == KDA],
            "full_attn_layers": [i for i, k in zip(held, kinds) if k != KDA],
            "num_heads": kda.n_heads, "head_dim": kda.head_dim,
            "short_conv_kernel_size": kda.conv_kernel},
        "intermediate_size": cfg.intermediate_dim,
        "moe_intermediate_size": moe.routed_intermediate_dim,
        "vocab_size": cfg.vocab_size,
        "hidden_act": "silu",
        "rms_norm_eps": cfg.rms_norm_eps,
        "rope_theta": cfg.rotary_base,
        "rope_scaling": None,
        "tie_word_embeddings": cfg.tie_word_embeddings,
        "model_max_length": cfg.max_position_embeddings or 1048576,
        "num_experts": moe.num_experts,
        "num_shared_experts": (moe.shared_intermediate_dim or 0)
        // moe.routed_intermediate_dim,
        "num_experts_per_token": moe.top_k,
        "moe_router_activation_func": "sigmoid",
        "moe_renormalize": moe.norm_topk_prob,
        "moe_layer_freq": 1,
        "num_expert_group": 1, "topk_group": 1, "use_grouped_topk": True,
        "routed_scaling_factor": moe.routed_scaling_factor,
        "num_nextn_predict_layers": cfg.n_nextn_predict_layers,
        "torch_dtype": "float32",
    }
    if cfg.held_layers is not None:
        d["held_layers"] = list(cfg.held_layers)
    if moe.is_share:
        d["num_routed_experts"] = moe.n_routed
        d["expert_shard_count"] = moe.n_routed // moe.num_experts
        d["expert_shard_index"] = moe.first_expert // moe.num_experts
    if moe.router_bias_init_std != 0.02:
        d["expert_bias_init_std"] = moe.router_bias_init_std
    return d


def _afmoe_config_dict(cfg: TransformerConfig) -> Dict[str, Any]:
    """The inverse of :func:`_afmoe_config`."""
    moe = cfg.moe
    names = {kind: name for name, kind in _HF_LAYER_TYPES.items()}
    kinds = [attention_kind(k) for k in cfg.layer_kinds]
    full = [i + 1 for i, k in enumerate(kinds) if k == FULL]
    d = {
        "model_type": "afmoe",
        "architectures": [_HF_ARCH["afmoe"]],
        "num_hidden_layers": cfg.n_layers,
        "num_dense_layers": (cfg.mlp_layer_types or ()).count(DENSE_FFN),
        "layer_types": [names[k] for k in kinds],
        "global_attn_every_n_layers": full[0] if full else cfg.n_layers + 1,
        "hidden_size": cfg.hidden_dim,
        "num_attention_heads": cfg.n_q_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "head_dim": cfg.head_dim,
        "intermediate_size": cfg.intermediate_dim,
        "moe_intermediate_size": moe.routed_intermediate_dim,
        "vocab_size": cfg.vocab_size,
        "hidden_act": "silu",
        "rms_norm_eps": cfg.rms_norm_eps,
        "rope_theta": cfg.rotary_base,
        "rope_scaling": None,
        "sliding_window": cfg.sliding_window,
        "tie_word_embeddings": cfg.tie_word_embeddings,
        "max_position_embeddings": cfg.max_position_embeddings or 32768,
        "mup_enabled": cfg.scale_embeddings,
        "num_experts": moe.num_experts,
        "num_experts_per_tok": moe.top_k,
        "num_shared_experts": (moe.shared_intermediate_dim or 0)
        // moe.routed_intermediate_dim,
        "score_func": moe.router_score,
        "route_norm": moe.norm_topk_prob,
        "route_scale": moe.routed_scaling_factor,
        "n_group": 1, "topk_group": 1,
        "num_expert_groups": 1, "num_limited_groups": 1,
        "load_balance_coeff": moe.aux_loss_coeff,
        "use_grouped_mm": True,
        "torch_dtype": "float32",
    }
    if moe.is_share:
        d["num_routed_experts"] = moe.n_routed
        d["expert_shard_count"] = moe.n_routed // moe.num_experts
        d["expert_shard_index"] = moe.first_expert // moe.num_experts
    return d


def _rope_dict(rope: RopeConfig) -> Dict[str, Any]:
    """One block of HF ``rope_parameters`` (the inverse of _rope_config)."""
    if rope.factor is None:
        return {"rope_type": "default", "rope_theta": rope.base}
    return {
        "rope_type": "yarn", "rope_theta": rope.base, "factor": rope.factor,
        "original_max_position_embeddings": rope.original_max_position,
        "beta_fast": rope.beta_fast, "beta_slow": rope.beta_slow,
        "attention_factor": rope.scale,
    }


# ---------------- sharded safetensors IO ----------------

SHARD_BYTES = 4 * 1024**3  # ~4GB per shard, HF convention


def save_hf_state_dict(
    sd: Dict[str, np.ndarray], save_dir: str, shard_bytes: int = SHARD_BYTES,
    n_threads: int = 8,
) -> None:
    """Write ``sd`` as sharded safetensors + index (threaded, one writer per
    shard — parity: reference saveload_utils.py threaded safetensor save)."""
    from safetensors.numpy import save_file

    os.makedirs(save_dir, exist_ok=True)
    shards: List[Dict[str, np.ndarray]] = [{}]
    sizes = [0]
    for k, v in sd.items():
        v = np.ascontiguousarray(v)
        nb = v.nbytes
        if sizes[-1] > 0 and sizes[-1] + nb > shard_bytes:
            shards.append({})
            sizes.append(0)
        shards[-1][k] = v
        sizes[-1] += nb
    n = len(shards)
    if n == 1:
        save_file(shards[0], os.path.join(save_dir, "model.safetensors"))
        return
    names = [
        f"model-{i + 1:05d}-of-{n:05d}.safetensors" for i in range(n)
    ]
    with ThreadPoolExecutor(max_workers=min(n_threads, n)) as ex:
        list(ex.map(
            lambda iv: save_file(
                shards[iv[0]], os.path.join(save_dir, iv[1])
            ),
            enumerate(names),
        ))
    index = {
        "metadata": {"total_size": int(sum(sizes))},
        "weight_map": {
            k: names[i] for i, shard in enumerate(shards) for k in shard
        },
    }
    with open(os.path.join(save_dir, "model.safetensors.index.json"), "w") as f:
        json.dump(index, f)


def load_hf_state_dict(load_dir: str, n_threads: int = 8) -> Dict[str, np.ndarray]:
    """Load a safetensors checkpoint dir (sharded or single-file); falls
    back to the legacy model.npz layout."""
    single = os.path.join(load_dir, "model.safetensors")
    index_path = os.path.join(load_dir, "model.safetensors.index.json")
    legacy = os.path.join(load_dir, "model.npz")
    from safetensors.numpy import load_file

    if os.path.exists(index_path):
        with open(index_path) as f:
            index = json.load(f)
        files = sorted(set(index["weight_map"].values()))
        out: Dict[str, np.ndarray] = {}
        with ThreadPoolExecutor(max_workers=min(n_threads, len(files))) as ex:
            for d in ex.map(
                lambda fn: load_file(os.path.join(load_dir, fn)), files
            ):
                out.update(d)
        return out
    if os.path.exists(single):
        return load_file(single)
    if os.path.exists(legacy):
        return dict(np.load(legacy))
    raise FileNotFoundError(f"no model.safetensors[.index.json] in {load_dir}")


# ---------------- high-level load/save ----------------


def load_hf_model(path_or_model, is_critic: bool = False, dtype: str = "float32"):
    """Load (config, params, tokenizer) from an HF model directory or an
    in-memory transformers model (used by tests)."""
    if isinstance(path_or_model, str):
        import transformers

        hf_cfg = transformers.AutoConfig.from_pretrained(path_or_model)
        model = transformers.AutoModelForCausalLM.from_pretrained(path_or_model)
        try:
            tokenizer = transformers.AutoTokenizer.from_pretrained(path_or_model)
        except Exception:
            tokenizer = None
    else:
        model = path_or_model
        hf_cfg = model.config
        tokenizer = None
    cfg = dataclasses.replace(config_from_hf(hf_cfg), is_critic=is_critic)
    params = params_from_hf_state_dict(model.state_dict(), cfg, dtype)
    return cfg, params, tokenizer


def save_hf_checkpoint(
    params, cfg: TransformerConfig, save_dir: str, meta: Optional[dict] = None
):
    """Publish weights in a layout consumable by BOTH the generation server
    (areal_tpu_config.json round-trip) and HF tooling (sharded safetensors +
    genuine config.json → transformers.AutoModelForCausalLM loads it).
    Replaces the r1/r2 npz layout (reference: hf_registry.py:32 save)."""
    os.makedirs(save_dir, exist_ok=True)
    sd = params_to_hf_state_dict(params, cfg)
    save_hf_state_dict(sd, save_dir)
    with open(os.path.join(save_dir, "config.json"), "w") as f:
        json.dump(hf_config_dict(cfg), f, indent=1)
    with open(os.path.join(save_dir, "areal_tpu_config.json"), "w") as f:
        json.dump(
            {"areal_tpu_config": dataclasses.asdict(cfg), "meta": meta or {}}, f
        )


def flatten_pytree(params, as_numpy: bool = False) -> Dict[str, Any]:
    """Nested-dict param pytree → flat {path: leaf} with '/'-joined keys.

    ``as_numpy=False`` keeps leaves verbatim (device arrays stay on
    device) — the weight-stream publisher/consumer use this so flattening
    a live tree never forces a d2h transfer; ``as_numpy=True`` converts
    for host serialization (checkpoint writers)."""
    out: Dict[str, Any] = {}

    def walk(prefix, node):
        if isinstance(node, dict):
            for k, v in node.items():
                walk(f"{prefix}/{k}" if prefix else str(k), v)
        else:
            out[prefix] = np.asarray(node) if as_numpy else node

    walk("", params)
    return out


def unflatten_pytree(flat: Dict[str, Any]) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for path, v in flat.items():
        parts = path.split("/")
        d = out
        for p in parts[:-1]:
            d = d.setdefault(p, {})
        d[parts[-1]] = v
    return out


# Back-compat aliases (pre-stream-sync private names).
def _flatten_pytree(params) -> Dict[str, np.ndarray]:
    return flatten_pytree(params, as_numpy=True)


_unflatten_pytree = unflatten_pytree


def save_native_checkpoint(
    params, cfg: TransformerConfig, save_dir: str, meta: Optional[dict] = None
):
    """The weight-SYNC format: the stacked param pytree saved verbatim as
    sharded safetensors — no HF-layout transposes, no re-stacking, dtype
    preserved (bf16 stays 2 bytes). The in-house generation server consumes
    this directly; HF layout (save_hf_checkpoint) is only needed for
    external-tooling interop. Replaces the reference's HF-format realloc
    dir (realhf/system/model_worker.py:1053 DISK path) with a layout that
    skips its conversion cost on both ends.

    ``areal_tpu_native.json`` is written LAST — it is the completeness
    sentinel consumers gate on."""
    os.makedirs(save_dir, exist_ok=True)
    save_hf_state_dict(_flatten_pytree(params), save_dir)
    with open(os.path.join(save_dir, "areal_tpu_native.json"), "w") as f:
        json.dump(
            {"areal_tpu_config": dataclasses.asdict(cfg), "meta": meta or {},
             "format": "native-pytree-v1"}, f
        )


def is_native_checkpoint(load_dir: str) -> bool:
    return os.path.exists(os.path.join(load_dir, "areal_tpu_native.json"))


def config_from_dict(cd: Dict[str, Any]) -> TransformerConfig:
    """``dataclasses.asdict(cfg)`` after a trip through JSON → the config:
    the nested dataclasses rebuilt, the lists tuples again."""
    cd = dict(cd)
    if cd.get("moe"):
        cd["moe"] = MoEConfig(**cd["moe"])
    if cd.get("ssm"):
        cd["ssm"] = SSMConfig(**cd["ssm"])
    if cd.get("dsa"):
        cd["dsa"] = SparseAttnConfig(**cd["dsa"])
    for key in ("layer_types", "mlp_layer_types"):
        if cd.get(key) is not None:
            cd[key] = tuple(cd[key])
    if cd.get("layer_rope") is not None:
        cd["layer_rope"] = tuple(
            (kind, None if rope is None else RopeConfig(**rope))
            for kind, rope in cd["layer_rope"])
    return TransformerConfig(**cd)


def load_native_checkpoint(load_dir: str):
    with open(os.path.join(load_dir, "areal_tpu_native.json")) as f:
        d = json.load(f)
    cfg = config_from_dict(d["areal_tpu_config"])
    params = _unflatten_pytree(load_hf_state_dict(load_dir))
    return cfg, params


def load_checkpoint_auto(load_dir: str):
    """Native if the dir is a weight-sync publish, else HF layout."""
    if is_native_checkpoint(load_dir):
        return load_native_checkpoint(load_dir)
    return load_hf_checkpoint(load_dir)


def load_hf_checkpoint(load_dir: str):
    acfg_path = os.path.join(load_dir, "areal_tpu_config.json")
    if not os.path.exists(acfg_path):
        # Legacy r2 layout kept config under config.json.
        acfg_path = os.path.join(load_dir, "config.json")
    with open(acfg_path) as f:
        d = json.load(f)
    cfg = config_from_dict(d["areal_tpu_config"])
    sd = load_hf_state_dict(load_dir)
    params = params_from_hf_state_dict(sd, cfg, cfg.dtype)
    return cfg, params
