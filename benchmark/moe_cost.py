"""Operation, byte and parameter counts of a sparse-expert transformer,
kept with the benchmark so that no later PR that claims a gain can move
them (as ``peaks.py`` keeps the dense ones). From HF config keys; no jax.
"""

from __future__ import annotations

from typing import Dict, Tuple


def expert_width(cfg: Dict) -> int:
    """Width of one expert: ``moe_intermediate_size`` where the family has
    the key, else ``intermediate_size`` (OLMoE, Mixtral)."""
    return int(cfg.get("moe_intermediate_size") or cfg["intermediate_size"])


def activated_matmul_params(cfg: Dict) -> int:
    """Parameters one token multiplies through in a forward pass: the
    attention projections, the router, ``num_experts_per_tok`` of the
    experts, and the output head. The embedding is a lookup and the norms
    multiply elementwise, so neither is counted — this is the N of
    6·N·T for an end-to-end utilisation, not the model's size."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg.get("head_dim") or d // nq
    attn = d * nq * dh + 2 * d * nkv * dh + nq * dh * d
    moe = (d * cfg["num_experts"]
           + cfg["num_experts_per_tok"] * 3 * d * expert_width(cfg))
    return cfg["num_hidden_layers"] * (attn + moe) + d * v


def grouped_ffn_cost(rows: float, calls: float, n_groups: int, d: int,
                     f: int, backward: bool, bytes_per_el: int = 2,
                     ) -> Tuple[float, float]:
    """(operations, bytes) the ALGORITHM needs for ``calls`` grouped
    gated-MLP calls (gate, up and down GEMMs) that together multiply
    ``rows`` (token, expert) rows of width ``d`` through experts of width
    ``f``, each call holding ``n_groups`` experts' weights. Forward: three
    GEMMs of 2·d·f a row; every row read and written once; the weights
    read once a call (the intermediates of width ``f`` need not leave the
    chip). Backward: twice the forward's operations (a GEMM for the rows'
    gradient and one for the weights' for each of the three); the rows,
    their output gradient and their input gradient move once, the weights
    are read and their gradient written once a call. Rows of the buffer
    that no expert owns (the static bound) need no work and count for
    nothing."""
    fwd_ops = 3 * 2 * rows * d * f
    w_el = calls * n_groups * 3 * d * f
    if not backward:
        return fwd_ops, bytes_per_el * (2 * rows * d + w_el)
    return 2 * fwd_ops, bytes_per_el * (3 * rows * d + 2 * w_el)
