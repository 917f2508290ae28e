"""Sharding rules: PartitionSpec trees for params and activations.

This module replaces the reference's entire tensor/sequence-parallel module
zoo (``realhf/impl/model/parallelism/tensor_parallel/modules.py`` — Column/
RowParallelLinear, ``mappings.py`` autograd collectives): on TPU the model
code stays pure (models/transformer.py) and parallelism is *data layout* —
a PartitionSpec pytree mirroring the param pytree plus a handful of
activation ``with_sharding_constraint`` points. XLA/GSPMD inserts the
all-reduces/all-gathers/reduce-scatters that Megatron hand-writes.

Conventions (axes from mesh.AXIS_ORDER):
 - batch dim of activations: ("dp", "fsdp", "ep")
 - sequence dim: "sp" (ring attention over this axis, parallel/ring.py)
 - heads / ffn dim of weights: "tp"; hidden dim of dense weights: "fsdp"
   and "ep" (ZeRO-3), of expert weights: "fsdp"
 - stacked-layer axis: "pp"; expert axis of MoE weights: "ep" (the MoE
   layer gathers tokens over it for each shard's experts, models/moe.py)
"""

from __future__ import annotations

from contextlib import contextmanager
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from areal_tpu.models.config import (
    ATTENTION_FREE_KINDS, CONV, CROSS, FULL, GDN, GMU, KDA, S6, SSD,
    TransformerConfig, attention_kind)
from areal_tpu.parallel.mesh import DATA_AXES

Params = Dict[str, Any]


def param_partition_specs(cfg: TransformerConfig) -> Params:
    """PartitionSpec tree with the same structure as
    ``models.transformer.init_params(cfg, ...)``.

    Megatron-equivalences (reference modules.py): wq/wk/wv/w_gate/w_up are
    ColumnParallelLinear → output dim on "tp"; wo/w_down are
    RowParallelLinear → input dim on "tp"; embedding is ParallelEmbedding →
    vocab on "tp". The *other* matrix dim goes to "fsdp" (ZeRO-3; the
    reference's DistributedOptimizer ZeRO-1 analogue, strengthened).
    """
    # ZeRO-3 axes of the dense weights' hidden dim: "fsdp", and "ep" too —
    # an ep shard owns its experts outright but only a slice of everything
    # else (masters, gradients, Adam moments), all-gathered where it is
    # used, as under fsdp. With the dense part replicated over ep instead,
    # OLMoE's step did not fit a 16 GB chip (PERF.md, PR 26).
    zero = ("fsdp", "ep")
    if cfg.is_hybrid:
        return _hybrid_partition_specs(cfg, zero)
    layers = _block_partition_specs(cfg, zero, "pp", dense_ffn=False)

    specs: Params = {
        "embedding": P("tp", zero),
        "layers": layers,
        "final_ln": P(None),
    }
    if cfg.norm_type == "layer":
        specs["final_ln_b"] = P(None)
    if cfg.pos_embedding == "learned":
        specs["pos_embedding"] = P(None, zero)
    if cfg.is_critic:
        specs["value_head"] = P(zero, None)
    elif not cfg.tie_word_embeddings:
        specs["lm_head"] = P(zero, "tp")
    return specs


def _block_partition_specs(cfg: TransformerConfig, zero, lead,
                           dense_ffn: bool, kind: str = FULL) -> Params:
    """The specs of whole blocks stacked on a leading axis split over
    ``lead`` ("pp", or None: a kind's stack of a tree per kind); the FFN
    is the expert layer where the model has one, unless ``dense_ffn``;
    the mixer by ``kind``: attention (a cross layer has q and o alone),
    an S6 or a Mamba-2 (SSD) mixer, a short convolution (CONV) or a gated
    memory unit — their
    matrices ZeRO-3 on the hidden dim, the channels whole (the scan and
    the memory it hands on are not split; nor are the heads under the
    one B/C group and the gated norm that spans them all)."""
    layers: Params = {
        "ln1": P(lead, None),
        "ln2": P(lead, None),
        "w_gate": P(lead, zero, "tp"),
        "w_up": P(lead, zero, "tp"),
        "w_down": P(lead, "tp", zero),
    }
    kind = attention_kind(kind)
    attends = kind not in ATTENTION_FREE_KINDS
    if kind == SSD:
        layers.update(_mamba_specs(lead, zero))
    elif kind == CONV:  # projections ZeRO-3, the channels and taps whole
        layers.update({
            "sc_in": P(lead, zero, None), "sc_out": P(lead, None, zero),
            "sc_conv": P(lead, None, None),
        })
    elif kind == GDN:  # as the Mamba-2 mixer: matrices ZeRO-3, heads whole
        layers.update({
            "gdn_qkvz": P(lead, zero, None), "gdn_ba": P(lead, zero, None),
            "gdn_out": P(lead, None, zero), "gdn_conv": P(lead, None, None),
            "gdn_dt_bias": P(lead, None), "gdn_A_log": P(lead, None),
            "gdn_norm": P(lead, None),
        })
    elif kind == KDA:  # likewise: matrices ZeRO-3 on the hidden dim, the
        # heads, the gates' bottlenecks and the taps whole
        layers.update({
            "kda_qkv": P(lead, zero, None), "kda_gates_a": P(lead, zero, None),
            "kda_out": P(lead, None, zero), "kda_conv": P(lead, None, None),
            "kda_f_b": P(lead, None, None), "kda_g_b": P(lead, None, None),
            "kda_dt_bias": P(lead, None), "kda_A_log": P(lead, None),
            "kda_norm": P(lead, None),
        })
    elif kind == S6:
        layers.update({
            "in_proj": P(lead, zero, None), "out_proj": P(lead, None, zero),
            "conv_w": P(lead, None, None), "conv_b": P(lead, None),
            "x_proj": P(lead, None, None), "dt_proj": P(lead, None, None),
            "dt_bias": P(lead, None), "A_log": P(lead, None, None),
            "D": P(lead, None),
        })
    elif kind == GMU:
        layers["gmu_in"] = P(lead, zero, None)
        layers["gmu_out"] = P(lead, None, zero)
    elif cfg.mla is not None:
        # latent attention (models/mla.py): the down-projections ZeRO-3 on
        # the hidden dim, the latents whole; the up-projections' columns
        # and o_proj's rows are by head, so "tp" splits heads as it does
        # for wq / wo; the latent norms replicated
        query = {"wq": P(lead, zero, "tp")} if cfg.mla.q_lora_rank is None else {
            "wq_a": P(lead, zero, None), "q_a_norm": P(lead, None),
            "wq_b": P(lead, zero, "tp")}
        layers.update({
            **query,
            "wkv_a": P(lead, zero, None), "kv_a_norm": P(lead, None),
            "wkv_b": P(lead, zero, "tp"), "wo": P(lead, "tp", zero),
        })
    else:
        layers["wq"] = P(lead, zero, "tp")
        layers["wo"] = P(lead, "tp", zero)
        if kind != CROSS:
            layers["wk"] = P(lead, zero, "tp")
            layers["wv"] = P(lead, zero, "tp")
        if cfg.differential_attention:
            for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2",
                         "subln"):
                layers[name] = P(lead, None)
    if cfg.use_attention_bias and attends:
        layers["bq"] = P(lead, "tp")
        if kind != CROSS:
            layers["bk"] = P(lead, "tp")
            layers["bv"] = P(lead, "tp")
    if cfg.use_attn_output_bias and attends:
        layers["bo"] = P(lead, None)
    if cfg.use_qk_norm and attends:
        layers["q_norm"] = P(lead, None)
        layers["k_norm"] = P(lead, None)
    if cfg.norm_type == "layer":
        layers["ln1_b"] = P(lead, None)
        layers["ln2_b"] = P(lead, None)
    if cfg.gated_attention and attends:
        layers["wg"] = P(lead, zero, "tp")
    if cfg.dsa is not None and attends:
        # the indexer (models/dsa.py): matrices ZeRO-3 on the hidden dim,
        # its heads whole (one selection for every head of the block)
        layers["indexer"] = {
            "wq": P(lead, zero, None), "wk": P(lead, zero, None),
            "ww": P(lead, zero, None), "k_norm": P(lead, None),
            "k_norm_b": P(lead, None)}
    if cfg.sandwich_norm:
        layers["ln1_post"] = P(lead, None)
        layers["ln2_post"] = P(lead, None)
    if cfg.mlp_type == "plain" and cfg.moe is None:
        layers["b_up"] = P(lead, "tp")
        layers["b_down"] = P(lead, None)
        for k in ("w_gate",):
            layers.pop(k, None)
    if cfg.moe is not None and not dense_ffn:
        # Experts stack on a leading axis [n, E, ...]; shard E over the
        # REAL "ep" axis (expert parallelism — each ep shard owns E/ep
        # experts, moe.py gathers the tokens for them), the ffn dim on tp,
        # and ZeRO-3 the remaining matrix dim over fsdp.
        layers["router"] = P(lead, None, None)
        if cfg.moe.router_score == "sigmoid":
            layers["router_bias"] = P(lead, None)
        layers["e_gate"] = P(lead, "ep", "fsdp", "tp")
        layers["e_up"] = P(lead, "ep", "fsdp", "tp")
        layers["e_down"] = P(lead, "ep", "tp", "fsdp")
        if cfg.moe.shared_intermediate_dim:
            layers["s_gate"] = P(lead, None, "tp")
            layers["s_up"] = P(lead, None, "tp")
            layers["s_down"] = P(lead, "tp", None)
            if cfg.moe.shared_expert_gate:
                layers["s_sig"] = P(lead, None, None)
        # Dense-MLP weights are absent in MoE layers.
        for k in ("w_gate", "w_up", "w_down"):
            del layers[k]
    return layers


def _mamba_specs(lead, zero) -> Params:
    """A Mamba-2 mixer's leaves (models/ssm.init_mamba_params)."""
    return {
        "in_proj": P(lead, zero, None), "out_proj": P(lead, None, zero),
        "conv_w": P(lead, None, None), "conv_b": P(lead, None),
        "dt_bias": P(lead, None), "A_log": P(lead, None),
        "D": P(lead, None), "norm": P(lead, None),
    }


def _hybrid_partition_specs(cfg: TransformerConfig, zero) -> Params:
    """The spec tree of a model whose layers are one mixer each
    (``params["layers"]`` a tree per kind): the matrices ZeRO-3 over
    ``zero`` on their hidden dim, attention's heads and the shared
    expert's width on "tp", everything else whole. A kind's stack is not
    split over "pp" (such a model is not pipelined: stages of unequal
    cost) and the held experts not over "ep" (latent, ungated experts run
    as a share, models/moe.py)."""
    from areal_tpu.models.config import (
        ATTENTION_ONLY, MAMBA, MIXER_KINDS, MOE_ONLY, has_dense_ffn)
    from areal_tpu.models.moe import moe_param_shapes

    # whole blocks whose FFN kinds differ (afmoe): a stack a kind
    layers: Params = {
        kind: _block_partition_specs(cfg, zero, None, has_dense_ffn(kind),
                                     kind)
        for kind in dict.fromkeys(cfg.layer_kinds) if kind not in MIXER_KINDS}
    if cfg.n_layers_of(MAMBA):
        layers[MAMBA] = {"ln": P(None, None), **_mamba_specs(None, zero)}
    if cfg.n_layers_of(ATTENTION_ONLY):
        layers[ATTENTION_ONLY] = {
            "ln": P(None, None),
            "wq": P(None, zero, "tp"), "wk": P(None, zero, "tp"),
            "wv": P(None, zero, "tp"), "wo": P(None, "tp", zero),
        }
    if cfg.n_layers_of(MOE_ONLY):
        by_name = {
            "router": P(None, None, None), "router_bias": P(None, None),
            "e_gate": P(None, None, "fsdp", "tp"),
            "e_up": P(None, None, "fsdp", "tp"),
            "e_down": P(None, None, "tp", "fsdp"),
            "latent_down": P(None, zero, None),
            "latent_up": P(None, None, zero),
            "s_gate": P(None, None, "tp"), "s_up": P(None, None, "tp"),
            "s_down": P(None, "tp", None),
        }
        layers[MOE_ONLY] = {"ln": P(None, None), **{
            name: by_name[name] for name in moe_param_shapes(cfg)}}
    specs: Params = {"embedding": P("tp", zero), "layers": layers,
                     "final_ln": P(None)}
    if cfg.norm_type == "layer":
        specs["final_ln_b"] = P(None)
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = P(zero, "tp")
    return specs


def named_shardings(mesh: Mesh, spec_tree: Params) -> Params:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def shard_params(params: Params, mesh: Mesh, cfg: TransformerConfig) -> Params:
    """Place a host/param pytree onto the mesh with the canonical layout."""
    shardings = named_shardings(mesh, param_partition_specs(cfg))
    return jax.tree.map(jax.device_put, params, shardings)


# ---------------- activation constraints ----------------
#
# Standard GSPMD sharding-hint points. The model code calls
# ``constrain(x, kind)``; outside a mesh context this is the identity, so
# models stay runnable without any parallelism setup (tests, CPU).

ACTIVATION_RULES: Dict[str, P] = {
    "tokens": P(DATA_AXES, "sp"),  # [B, T]
    "hidden": P(DATA_AXES, "sp", None),  # [B, T, D]
    "logits": P(DATA_AXES, "sp", "tp"),  # [B, T, V]
    "heads": P(DATA_AXES, "sp", "tp", None),  # [B, T, H, Dh]
    "kv_cache": P(None, DATA_AXES, None, "tp", None),  # [n, B, S, Hkv, Dh]
    # Decode mode: T == new-token count (typically 1) — never shard it.
    "hidden_decode": P(DATA_AXES, None, None),
    "logits_decode": P(DATA_AXES, None, "tp"),
}

def rules_without_axes(axes, rules: Optional[Dict[str, P]] = None
                       ) -> Dict[str, P]:
    """ACTIVATION_RULES with the given mesh axes stripped from every spec
    — for code traced inside a shard_map that is manual over ``axes``
    (parallel/pipeline.py's PP∘SP stages), where a
    with_sharding_constraint naming a manual axis is an error. Tuple
    entries drop the stripped members; entries that become empty turn into
    None."""
    axes = frozenset(axes)
    out: Dict[str, P] = {}
    for kind, spec in (rules or ACTIVATION_RULES).items():
        parts = []
        for p in spec:
            if isinstance(p, (tuple, list)):
                kept = tuple(a for a in p if a not in axes)
                parts.append(kept if kept else None)
            else:
                parts.append(None if p in axes else p)
        out[kind] = P(*parts)
    return out


@contextmanager
def strip_manual_axes(axes):
    """Re-push the innermost activation_sharding context with ``axes``
    stripped from every rule (no-op when no context is active). For code
    traced inside a shard_map manual over ``axes`` whose trace point is
    NOT lexically inside the caller's own stripped-rules push — e.g. a
    custom_vjp backward traced long after the forward's context popped,
    with only the engine's full-rules context left on the stack."""
    if not _ACTIVE:
        yield
        return
    mesh, rules = _ACTIVE[-1]
    with activation_sharding(mesh, rules_without_axes(axes, rules)):
        yield


_ACTIVE: list = []  # stack of (mesh, rules)


@contextmanager
def activation_sharding(mesh: Mesh, rules: Optional[Dict[str, P]] = None):
    _ACTIVE.append((mesh, rules or ACTIVATION_RULES))
    try:
        yield
    finally:
        _ACTIVE.pop()


def current_mesh() -> Optional[Mesh]:
    """The mesh of the innermost activation_sharding context (or None)."""
    return _ACTIVE[-1][0] if _ACTIVE else None


def constrain(x: jnp.ndarray, kind: str) -> jnp.ndarray:
    if not _ACTIVE:
        return x
    mesh, rules = _ACTIVE[-1]
    spec = rules.get(kind)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
