"""Transformer configuration.

Parity target: ``ReaLModelConfig`` (reference realhf/api/core/model_api.py:340)
and the per-family HF conversion registry (realhf/api/from_hf/*.py). Families
are expressed as pure config differences (bias flags, qk-norm, tying), not
separate model classes.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

# The kinds a layer can have (``TransformerConfig.layer_kinds``). FULL and
# SLIDING are whole blocks (attention of that kind, then the MLP). The
# others are ONE mixer alone, ``h + f(norm(h))`` (nemotron_h's hybrid
# pattern): a Mamba-2 state-space mixer, an expert layer, or attention
# with no position embedding.
FULL, SLIDING = "full", "sliding"
MAMBA, MOE_ONLY, ATTENTION_ONLY = "mamba", "moe_only", "attention_only"
MIXER_KINDS = (MAMBA, MOE_ONLY, ATTENTION_ONLY)
# Whole blocks (a mixer, then the dense MLP) of a decoder-hybrid-decoder
# model (phi4flash / SambaY), beside FULL and SLIDING: S6 a selective-scan
# (Mamba-1) mixer; GMU a gated memory unit, which has no scan of its own
# and gates the scan output ``m`` that ONE earlier S6 layer made; CROSS
# attention with a query projection alone, over the K/V that ONE earlier
# FULL layer made (``TransformerConfig.memory_source`` / ``kv_source``).
S6, GMU, CROSS = "s6", "gmu", "cross"
SAMBAY_KINDS = (S6, GMU, CROSS)
# A whole block whose mixer is the Mamba-2 (SSD) mixer that MAMBA runs
# alone, then the dense MLP (granitemoehybrid's ``mamba`` layers, beside
# FULL blocks without a position embedding).
SSD = "ssd"
# A whole block whose mixer is a Gated DeltaNet linear-attention mixer
# (models/gdn.py), then the model's FFN — the expert layer where it has
# one (qwen3_next's ``linear_attention`` layers, beside FULL blocks).
GDN = "gdn"
# A whole block whose mixer is a Kimi Delta Attention mixer (models/kda.py:
# the delta rule with a decay a key channel), then the model's FFN — its
# dense MLP on the leading blocks (KDA + ``DENSE_SUFFIX``), the expert
# layer after them (kimi_linear's ``kda_layers``, beside FULL blocks of
# latent attention).
KDA = "kda"
# A whole block whose mixer is a doubly gated short convolution
# (models/shortconv.py: no head, no state, no position), then the model's
# FFN — its dense MLP on the leading blocks (CONV + ``DENSE_SUFFIX``), the
# expert layer after them (lfm2_moe's ``conv`` layers, beside FULL blocks).
CONV = "conv"
# Whole blocks whose mixer is not self-attention: no K/V cache decodes
# them, and their parameter shapes are their kind's own.
BLOCK_MIXER_KINDS = SAMBAY_KINDS + (SSD, GDN, KDA, CONV)
# Of those, the blocks that run no attention at all: no q/k/v/o, no q/k
# norm, no attention gate (a CROSS block still has q and o).
ATTENTION_FREE_KINDS = (S6, GMU, SSD, GDN, KDA, CONV)
# What a layer hands on to later layers, by the name the readers ask for.
MEMORY, SHARED_KV = "memory", "kv"
# A whole block's FFN kind (HF ``mlp_layer_types``): the model's dense MLP
# or its expert layer. A block has an attention kind AND an FFN kind; the
# kind of a block whose FFN is the dense MLP of a model that has experts
# (afmoe's leading blocks) is its attention kind with ``DENSE_SUFFIX``.
DENSE_FFN, SPARSE_FFN = "dense", "sparse"
DENSE_SUFFIX = "_dense"


def attention_kind(kind: str) -> str:
    """The attention kind of a whole block's kind."""
    return kind.removesuffix(DENSE_SUFFIX)


def has_dense_ffn(kind: str) -> bool:
    """A whole block whose FFN is the dense MLP though the model has
    experts."""
    return kind.endswith(DENSE_SUFFIX)


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """Mirrors ReaLMoEConfig (reference model_api.py:294)."""

    # Experts whose weights this model holds (``e_gate`` [E, ...]).
    num_experts: int = 8
    top_k: int = 2
    # A SHARE of an expert layer: the model was published with
    # ``router_experts`` experts (the router's width) and this process
    # holds ``num_experts`` of them, from index ``first_expert`` on — one
    # rank's part of an expert-parallel group, run without the others.
    # The router scores all of them, the gates are normalised over all
    # chosen ones, and a pair that chose an expert held elsewhere adds
    # nothing here. None = every expert is held.
    router_experts: Optional[int] = None
    first_expert: int = 0
    # Expert-buffer size multiplier: capacity per expert is
    # ceil(top_k * n_tokens * capacity_factor / num_experts); overflow
    # tokens are dropped (contribute nothing), mirroring the reference's
    # token_dispatcher capacity drop. None = dropless (olmoe): no
    # capacity, every chosen (token, expert) pair is computed.
    capacity_factor: Optional[float] = 2.0
    routed_intermediate_dim: Optional[int] = None
    # qwen-moe style always-on shared expert; None = no shared expert
    shared_intermediate_dim: Optional[int] = None
    aux_loss_coeff: float = 1e-3
    z_loss_coeff: float = 0.0
    input_jitter_eps: float = 0.0
    norm_topk_prob: bool = True
    # The shared expert's output times ``sigmoid(x · s_sig)``, a gate a
    # token (qwen2_moe, qwen3_next: ``shared_expert_gate`` [D, 1]).
    shared_expert_gate: bool = False
    # What the router makes of its logits — a property of the family, set
    # by the HF mapping: "softmax" (gates are the top-k probabilities), or
    # "sigmoid" (nemotron_h, deepseek-v3: each expert's score is a sigmoid
    # of its own logit; the choice is the top-k of score + ``router_bias``,
    # a buffer that takes no gradient; the gates are the chosen SCORES).
    router_score: str = "softmax"
    # What ``init_params`` draws ``router_bias`` at, N(0, this) (this
    # repo's key ``expert_bias_init_std`` of the lfm2_moe mapping; every
    # other weight of the layer, and the bias elsewhere, N(0, 0.02)). A
    # trained bias keeps the experts evenly chosen; a drawn one skews them,
    # and the load of a SHARE of the layer swings with the draw.
    router_bias_init_std: float = 0.02
    # multiplies the (renormalised) gates (HF ``routed_scaling_factor``)
    routed_scaling_factor: float = 1.0
    # Experts that work in a LATENT width (HF ``moe_latent_size``): the
    # tokens are projected hidden -> latent before the dispatch and the
    # combined expert outputs latent -> hidden after it; the router and
    # the shared expert read the hidden width. None = experts at hidden.
    latent_dim: Optional[int] = None
    # The experts' (and the shared expert's) MLP: gated (act(x·gate) *
    # x·up) · down, or plain act(x·up) · down; "silu" | "relu2".
    gated_experts: bool = True
    expert_act: str = "silu"

    @property
    def n_routed(self) -> int:
        """Experts the router chooses among (its width)."""
        return self.router_experts or self.num_experts

    @property
    def is_share(self) -> bool:
        return self.n_routed != self.num_experts


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """A Mamba-2 mixer's sizes (HF nemotron_h keys ``mamba_num_heads``,
    ``mamba_head_dim``, ``n_groups``, ``ssm_state_size``, ``conv_kernel``,
    ``chunk_size``) and the ranges its init draws from
    (``time_step_{min,max,floor}``). A head reads the B/C group
    ``head // (n_heads / n_groups)``; the gated norm spans each group's
    ``d_inner / n_groups`` channels."""

    n_heads: int
    head_dim: int
    n_groups: int
    state_dim: int
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4

    @property
    def d_inner(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the depthwise convolution runs over: x, B and C."""
        return self.d_inner + 2 * self.n_groups * self.state_dim

    @property
    def in_proj_dim(self) -> int:
        """[z | xBC | dt]."""
        return self.d_inner + self.conv_dim + self.n_heads


@dataclasses.dataclass(frozen=True)
class GDNConfig:
    """A Gated DeltaNet mixer's sizes (HF qwen3_next keys
    ``linear_num_key_heads``, ``linear_num_value_heads``,
    ``linear_key_head_dim``, ``linear_value_head_dim``,
    ``linear_conv_kernel_dim``). Value head ``i`` reads key head ``i //
    (n_v_heads / n_k_heads)``; the rule runs in chunks of ``chunk_size``
    tokens (the program's choice: no key of the model)."""

    n_k_heads: int
    n_v_heads: int
    k_head_dim: int
    v_head_dim: int
    conv_kernel: int = 4
    chunk_size: int = 64

    @property
    def key_dim(self) -> int:
        return self.n_k_heads * self.k_head_dim

    @property
    def value_dim(self) -> int:
        return self.n_v_heads * self.v_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the depthwise convolution runs over: q, k and v."""
        return 2 * self.key_dim + self.value_dim

    @property
    def qkvz_dim(self) -> int:
        """[q | k | v | z] (by key head in HF's layout)."""
        return 2 * self.key_dim + 2 * self.value_dim

    @property
    def ba_dim(self) -> int:
        """[b | a], one of each a value head."""
        return 2 * self.n_v_heads


@dataclasses.dataclass(frozen=True)
class KDAConfig:
    """A Kimi Delta Attention mixer's sizes (HF kimi_linear's
    ``linear_attn_config``: ``num_heads``, ``head_dim`` — key and value
    alike —, ``short_conv_kernel_size``). The decay's and the output
    gate's projections go through a bottleneck of ``gate_rank`` (the
    family's: one head's width); the rule runs in chunks of ``chunk_size``
    tokens (the program's choice: no key of the model)."""

    n_heads: int
    head_dim: int
    conv_kernel: int = 4
    chunk_size: int = 64

    @property
    def gate_rank(self) -> int:
        """The bottleneck of the decay's and the output gate's projections:
        one head's width in this family (no key of the model sets it)."""
        return self.head_dim

    @property
    def gates_a_dim(self) -> int:
        """[b | f | z]: β a head, and the two gates' bottlenecks."""
        return self.n_heads + 2 * self.gate_rank


@dataclasses.dataclass(frozen=True)
class ShortConvConfig:
    """A doubly gated short convolution's sizes (HF lfm2 / lfm2_moe key
    ``conv_L_cache``): ``kernel`` taps, depthwise over the model's hidden
    width, no bias, no activation (models/shortconv.py)."""

    kernel: int = 3


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention's five sizes (HF deepseek_v2 / _v3 /
    glm4_moe_lite keys of the same names; models/mla.py). A query or key
    head is ``[nope | rope]`` — ``qk_nope_head_dim`` dims that carry no
    position, then ``qk_rope_head_dim`` that RoPE turns, the key's rotary
    part ONE vector a token shared by every head — and
    ``TransformerConfig.head_dim`` is their sum; queries come through a
    normed latent of ``q_lora_rank``, keys' nope parts and values through
    ONE normed latent of ``kv_lora_rank``."""

    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def kv_a_dim(self) -> int:
        """[c_kv | k_rope]: the down-projection's output."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def kv_b_head_dim(self) -> int:
        """[k_nope | v]: one head of the up-projection's output."""
        return self.qk_nope_head_dim + self.v_head_dim


@dataclasses.dataclass(frozen=True)
class SparseAttnConfig:
    """Learned sparse attention's sizes (HF KeyeVL2's ``sa_config``:
    ``indexer_num_heads``, ``indexer_head_dim``, ``topk``; ONE indexer key
    head, ``indexer_num_kv_heads`` 1; models/dsa.py): a lightning indexer
    of ``n_heads`` query heads of ``head_dim`` scores every earlier token
    of the document, and the ``top_k`` best are the only keys a query
    attends. ``q_tile`` / ``kv_tile`` (``q_chunk_size`` /
    ``kv_chunk_size``) are carried as the tiling they are read as: the
    blocks in which the scores are made, which change no result."""

    n_heads: int
    head_dim: int
    top_k: int
    q_tile: int = 512
    kv_tile: int = 512

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim


@dataclasses.dataclass(frozen=True)
class S6Config:
    """A Mamba-1 (selective scan, S6) mixer's sizes: ``d_inner`` channels,
    each with ``state_dim`` states whose decay is its own (``A`` is
    ``[d_inner, state_dim]``: nothing here is a head or a group, and no
    chunk size is the model's), a depthwise convolution of ``conv_kernel``
    taps, and Δ through a bottleneck of ``dt_rank``; the ranges its init
    draws Δ's bias from as Mamba-1's defaults."""

    d_inner: int
    state_dim: int = 16
    conv_kernel: int = 4
    dt_rank: int = 0
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4

    @property
    def x_proj_dim(self) -> int:
        """[δ | B | C]."""
        return self.dt_rank + 2 * self.state_dim


@dataclasses.dataclass(frozen=True)
class RopeConfig:
    """One RoPE table: plain (``factor`` None) or YaRN as ``transformers``
    computes it (``_compute_yarn_parameters``)."""

    base: float = 10000.0
    factor: Optional[float] = None
    original_max_position: Optional[int] = None
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    # multiplies cos and sin; None = 0.1 * ln(factor) + 1
    attention_factor: Optional[float] = None

    @property
    def scale(self) -> float:
        if self.factor is None:
            return 1.0
        if self.attention_factor is not None:
            return self.attention_factor
        return 0.1 * math.log(self.factor) + 1.0


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    n_layers: int
    hidden_dim: int
    n_q_heads: int
    n_kv_heads: int
    head_dim: int
    intermediate_dim: int
    vocab_size: int
    rotary_base: float = 10000.0
    rms_norm_eps: float = 1e-6
    use_attention_bias: bool = False  # qwen2: True on qkv
    use_attn_output_bias: bool = False
    use_qk_norm: bool = False  # qwen3, olmoe
    # What one q/k RMSNorm spans — a property of the family, set by the HF
    # mapping (models/hf.py): "head" = each head's head_dim after the
    # split into heads (qwen3), "proj" = the whole projected vector
    # before it (olmoe: q_norm [q_dim], k_norm [kv_dim]).
    qk_norm_extent: str = "head"
    tie_word_embeddings: bool = False
    is_critic: bool = False  # scalar head instead of lm head
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None  # the MAMBA layers' / SSD blocks' mixer
    s6: Optional[S6Config] = None  # the S6 blocks' mixer (GMU reads its width)
    gdn: Optional[GDNConfig] = None  # the GDN blocks' mixer
    kda: Optional[KDAConfig] = None  # the KDA blocks' mixer
    shortconv: Optional[ShortConvConfig] = None  # the CONV blocks' mixer
    # Latent attention in every ATTENTION block (the blocks of a mixer
    # kind beside them are their kind's): q, k and v are not three
    # projections of the hidden state (models/mla.py); ``head_dim`` is the
    # query / key head's ``nope + rope``, ``n_kv_heads == n_q_heads``; a
    # value head is ``mla.v_head_dim`` wide.
    mla: Optional[MLAConfig] = None
    # Learned sparse attention in every FULL block (models/dsa.py): an
    # indexer beside q, k, v picks the keys a query attends. The blocks
    # stay FULL: no layer kind of its own.
    dsa: Optional[SparseAttnConfig] = None
    # sliding window attention (mistral/gemma2); None = full attention
    sliding_window: Optional[int] = None
    # The kind of each layer: FULL or SLIDING (HF ``layer_types``), or one
    # of MIXER_KINDS (HF ``hybrid_override_pattern``);
    # None = every layer alike: SLIDING where ``sliding_window`` is set.
    layer_types: Optional[Tuple[str, ...]] = None
    # (attention kind, RopeConfig) for the kinds whose RoPE is not the
    # plain table at ``rotary_base`` (HF ``rope_parameters``, one block a
    # layer type); None in place of a RopeConfig = the layers of that kind
    # carry no position embedding (afmoe's full-attention layers).
    layer_rope: Optional[Tuple[Tuple[str, Optional[RopeConfig]], ...]] = None
    # The FFN kind of each whole block of a model that has experts,
    # DENSE_FFN or SPARSE_FFN (HF ``mlp_layer_types``; afmoe's
    # ``num_dense_layers``); None = every block runs the experts.
    mlp_layer_types: Optional[Tuple[str, ...]] = None
    # Gated attention (afmoe): a fifth projection ``wg`` [D, q_dim]; the
    # attention output is multiplied by sigmoid(x wg) before ``wo``.
    gated_attention: bool = False
    # RoPE on the first ``partial_rotary_factor`` of each head's dims
    # (rotate-half inside them), the others untouched (qwen3_next: 0.25).
    partial_rotary_factor: float = 1.0
    # Every RMSNorm weight of the model — the residual norms, the final
    # norm, the per-head q/k norms — is ZERO-CENTRED: ``x̂ (1 + w)``, ``w``
    # drawn 0 (qwen3_next; the GDN mixer's gated output norm alone is a
    # plain ``x̂ w``).
    zero_centered_norm: bool = False
    # Differential attention (phi4flash): q's heads are pairs (2p, 2p+1),
    # k's likewise, v's pairs one value of twice the head size; a pair's
    # output is softmax(q1 k1) v - lambda * softmax(q2 k2) v, RMS-normed
    # over the value's width (``subln``) and scaled by 1 - lambda_init,
    # with lambda_init = 0.8 - 0.6 exp(-0.3 l) at the PUBLISHED layer
    # index l = ``first_layer_index`` + the layer's index here, and lambda
    # from four learned vectors of head_dim a layer.
    differential_attention: bool = False
    # The published index of this model's layer 0 (a cut in depth that
    # starts inside the published stack).
    first_layer_index: int = 0
    # The published (1-based) block numbers this model's layers are, of a
    # cut in depth that is NOT one contiguous run (kimi_linear's key
    # ``held_layers`` of this repo: block 1 and one whole period further
    # in); None = layers ``first_layer_index`` on, in order.
    held_layers: Optional[Tuple[int, ...]] = None
    # Sandwich norms (afmoe): a second norm on each branch's OUTPUT before
    # it is added to the residual stream (``ln1_post``, ``ln2_post``).
    sandwich_norm: bool = False
    # MLP activation: "silu" (llama family), "gelu_tanh" (gemma/gpt2),
    # "gelu" (exact)
    hidden_act: str = "silu"
    # "gated" = SwiGLU/GeGLU (w_gate/w_up/w_down); "plain" = act(x@w_up)@w_down
    # with biases (gpt2)
    mlp_type: str = "gated"
    norm_type: str = "rms"  # "rms" | "layer" (gpt2 LayerNorm with bias)
    # "rope" | "learned" (gpt2 absolute position table) | "none" (the
    # attention layers of a hybrid model carry no position embedding)
    pos_embedding: str = "rope"
    max_position_embeddings: Optional[int] = None  # learned-pos table size
    scale_embeddings: bool = False  # gemma: hidden *= sqrt(hidden_dim)
    # The granite family's four multipliers, each the identity by default:
    # the embedding's output times ``embedding_multiplier``; BOTH branches
    # of every block times ``residual_multiplier`` before they are added
    # to the residual stream; ``attention_multiplier`` the softmax scale
    # IN PLACE of head_dim ** -0.5 (None); the logits divided by
    # ``logits_scaling`` — in the head, and so in every loss and logprob
    # read from it. A tied embedding is then read at two scales.
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None
    logits_scaling: float = 1.0
    # Multi-token-prediction modules the family publishes behind its last
    # block (HF ``num_nextn_predict_layers``): carried through config.json
    # both ways; nothing of them is built, and their weights
    # (``model.layers.<n_layers>.*`` on) are skipped on load by name.
    n_nextn_predict_layers: int = 0
    # HF family tag driving weight-name mapping + config.json emission
    # (models/hf.py); None for fabricated test configs.
    hf_family: Optional[str] = None
    dtype: str = "float32"  # param dtype; compute dtype chosen at call site

    @property
    def q_dim(self) -> int:
        return self.n_q_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    @property
    def o_dim(self) -> int:
        """The attention output's width, ``wo``'s rows: a value head a
        query head (latent attention's may be narrower than the key)."""
        if self.mla is not None:
            return self.n_q_heads * self.mla.v_head_dim
        return self.q_dim

    @property
    def q_norm_dim(self) -> int:
        return self.q_dim if self.qk_norm_extent == "proj" else self.head_dim

    @property
    def k_norm_dim(self) -> int:
        return self.kv_dim if self.qk_norm_extent == "proj" else self.head_dim

    @property
    def rotary_dim(self) -> int:
        """Dims of a head that RoPE turns (the table's width): the first
        ``partial_rotary_factor`` of them, or latent attention's LAST
        ``qk_rope_head_dim`` (models/mla.py turns them itself)."""
        if self.mla is not None:
            if self.pos_embedding != "rope":  # the shared key un-rotated
                return 0
            return self.mla.qk_rope_head_dim
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def group_size(self) -> int:
        assert self.n_q_heads % self.n_kv_heads == 0
        return self.n_q_heads // self.n_kv_heads

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """The kind of every layer: its attention (or mixer) kind, with
        ``DENSE_SUFFIX`` where a model with experts runs its dense MLP."""
        if self.layer_types is not None:
            assert len(self.layer_types) == self.n_layers, (
                f"{len(self.layer_types)} layer_types for "
                f"{self.n_layers} layers")
            kinds = tuple(self.layer_types)
        else:
            kind = SLIDING if self.sliding_window is not None else FULL
            kinds = (kind,) * self.n_layers
        if self.mlp_layer_types is None or self.moe is None:
            return kinds
        assert len(self.mlp_layer_types) == self.n_layers, (
            f"{len(self.mlp_layer_types)} mlp_layer_types for "
            f"{self.n_layers} layers")
        return tuple(k + DENSE_SUFFIX if f == DENSE_FFN else k
                     for k, f in zip(kinds, self.mlp_layer_types))

    @property
    def period_kinds(self) -> Tuple[str, ...]:
        """One period of the layer pattern: the shortest prefix of
        ``layer_kinds`` that, repeated, gives all of it. The layer scan
        runs over periods (models/transformer.py); a model whose layers
        are alike has a period of one layer."""
        kinds = self.layer_kinds
        if self.cross_layer_reads:  # a source is ONE layer of the pattern
            return kinds
        return next(
            kinds[:p] for p in range(1, len(kinds) + 1)
            if len(kinds) % p == 0 and kinds[:p] * (len(kinds) // p) == kinds)

    @property
    def has_mixer_layers(self) -> bool:
        """Layers that are one mixer alone (no cache to decode from)."""
        return any(k in MIXER_KINDS for k in self.layer_kinds)

    @property
    def has_cacheless_layers(self) -> bool:
        """Layers no K/V cache can decode: a mixer alone, a state-space
        block, a layer that reads what another layer made, or latent
        attention (its cache is the latent's), or attention under a learned
        selection (the indexer's key has no cache)."""
        return (self.mla is not None or self.dsa is not None
                or self.has_mixer_layers) or any(
            attention_kind(k) in BLOCK_MIXER_KINDS for k in self.layer_kinds)

    @property
    def is_hybrid(self) -> bool:
        """Layers whose parameter SHAPES differ by kind — one mixer alone,
        whole blocks whose mixers differ, or whole blocks some of which
        run a dense MLP and some the experts: ``params["layers"]`` is
        then a tree per KIND, each
        stacked over that kind's layers."""
        return any(k in MIXER_KINDS or attention_kind(k) in BLOCK_MIXER_KINDS
                   or has_dense_ffn(k) for k in self.layer_kinds)

    @property
    def memory_source(self) -> Optional[int]:
        """The layer whose scan output the GMU layers gate: the last S6
        layer before the first GMU one. None where no layer reads it."""
        kinds = self.layer_kinds
        if GMU not in kinds:
            return None
        before = [i for i in range(kinds.index(GMU)) if kinds[i] == S6]
        assert before, "a gated memory unit with no S6 layer before it"
        return before[-1]

    @property
    def kv_source(self) -> Optional[int]:
        """The layer whose K/V the CROSS layers attend over: the last
        FULL layer before the first CROSS one. None where none reads it."""
        kinds = self.layer_kinds
        if CROSS not in kinds:
            return None
        before = [i for i in range(kinds.index(CROSS)) if kinds[i] == FULL]
        assert before, "a cross-attention layer with no full one before it"
        return before[-1]

    @property
    def cross_layer_reads(self) -> Dict[str, int]:
        """{what is handed on: layers that read it} — empty for a model
        whose layers read the residual stream alone."""
        reads = {MEMORY: self.layer_kinds.count(GMU),
                 SHARED_KV: self.layer_kinds.count(CROSS)}
        return {name: n for name, n in reads.items() if n}

    def handed_on_by(self, layer: int) -> Optional[str]:
        """What layer ``layer`` hands on to later layers, or None."""
        if layer == self.memory_source:
            return MEMORY
        if layer == self.kv_source:
            return SHARED_KV
        return None

    def has_mixer(self, kind: str) -> bool:
        """Whether any whole block's mixer is ``kind``, whatever its FFN."""
        return kind in map(attention_kind, self.layer_kinds)

    def n_layers_of(self, kind: str) -> int:
        return self.layer_kinds.count(kind)

    @property
    def n_expert_layers(self) -> int:
        if self.moe is None:
            return 0
        return sum(k not in (MAMBA, ATTENTION_ONLY) and not has_dense_ffn(k)
                   for k in self.layer_kinds)

    def block_counts(self) -> Dict[str, int]:
        """{"<attention or mixer kind>/<dense | experts | ->": layers}."""
        def ffn(kind):
            if kind in (MAMBA, ATTENTION_ONLY):
                return "-"
            return "experts" if self.moe is not None and not has_dense_ffn(
                kind) else "dense"

        counts: Dict[str, int] = {}
        for kind in self.layer_kinds:
            key = f"{attention_kind(kind)}/{ffn(kind)}"
            counts[key] = counts.get(key, 0) + 1
        return counts

    def window_of(self, kind: str) -> Optional[int]:
        return self.sliding_window if attention_kind(kind) == SLIDING else None

    def attention_windows(self) -> Dict[Optional[int], int]:
        """{window (None: full causal): layers that run causal
        self-attention over a packed row under it}."""
        counts: Dict[Optional[int], int] = {}
        for kind in self.layer_kinds:
            if attention_kind(kind) in (FULL, SLIDING, ATTENTION_ONLY, CROSS):
                window = self.window_of(kind)
                counts[window] = counts.get(window, 0) + 1
        return counts

    def rope_of(self, kind: str) -> Optional[RopeConfig]:
        """The RoPE table of a layer kind; None = no position embedding."""
        return dict(self.layer_rope or ()).get(
            attention_kind(kind), RopeConfig(base=self.rotary_base))


def tiny_config(
    vocab_size: int = 128,
    n_layers: int = 2,
    hidden_dim: int = 32,
    n_q_heads: int = 4,
    n_kv_heads: int = 2,
    is_critic: bool = False,
    **kw,
) -> TransformerConfig:
    """Small fabricated config for tests (reference testing.py:37-43).

    A ``moe`` kwarg may be a plain dict (the YAML/CLI ``actor.tiny.moe``
    form) — it is coerced to :class:`MoEConfig` here so every downstream
    consumer sees the dataclass.
    """
    if isinstance(kw.get("moe"), dict):
        kw["moe"] = MoEConfig(**kw["moe"])
    return TransformerConfig(
        n_layers=n_layers,
        hidden_dim=hidden_dim,
        n_q_heads=n_q_heads,
        n_kv_heads=n_kv_heads,
        head_dim=hidden_dim // n_q_heads,
        intermediate_dim=hidden_dim * 2,
        vocab_size=vocab_size,
        is_critic=is_critic,
        **kw,
    )
