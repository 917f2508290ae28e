"""Transformer configuration.

Parity target: ``ReaLModelConfig`` (reference realhf/api/core/model_api.py:340)
and the per-family HF conversion registry (realhf/api/from_hf/*.py). Families
are expressed as pure config differences (bias flags, qk-norm, tying), not
separate model classes.
"""

from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """Mirrors ReaLMoEConfig (reference model_api.py:294)."""

    num_experts: int = 8
    top_k: int = 2
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
    # sliding window attention (mistral/gemma2); None = full attention
    sliding_window: Optional[int] = None
    # MLP activation: "silu" (llama family), "gelu_tanh" (gemma/gpt2),
    # "gelu" (exact)
    hidden_act: str = "silu"
    # "gated" = SwiGLU/GeGLU (w_gate/w_up/w_down); "plain" = act(x@w_up)@w_down
    # with biases (gpt2)
    mlp_type: str = "gated"
    norm_type: str = "rms"  # "rms" | "layer" (gpt2 LayerNorm with bias)
    # "rope" | "learned" (gpt2 absolute position table)
    pos_embedding: str = "rope"
    max_position_embeddings: Optional[int] = None  # learned-pos table size
    scale_embeddings: bool = False  # gemma: hidden *= sqrt(hidden_dim)
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
    def q_norm_dim(self) -> int:
        return self.q_dim if self.qk_norm_extent == "proj" else self.head_dim

    @property
    def k_norm_dim(self) -> int:
        return self.kv_dim if self.qk_norm_extent == "proj" else self.head_dim

    @property
    def group_size(self) -> int:
        assert self.n_q_heads % self.n_kv_heads == 0
        return self.n_q_heads // self.n_kv_heads


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
