"""Operation, byte and parameter counts of a hybrid model's new layers —
the chunked state-space scan (SSD) and a two-matmul (ungated) expert in a
latent width — kept with the benchmark so that no later PR that claims a
gain can move them (as ``peaks.py`` and ``moe_cost.py`` keep theirs). From
HF nemotron_h config keys; no jax.
"""

from __future__ import annotations

from typing import Dict, Tuple


def ssd_scan_cost(rows: int, length: int, chunk: int, heads: int,
                  head_dim: int, groups: int, state: int, backward: bool,
                  bytes_per_el: int = 2) -> Tuple[float, float]:
    """(operations, bytes) the ALGORITHM needs for one chunked scan over a
    packed [rows, length] grid: per chunk of Q tokens, C·B^T a group
    (2·Q²·N), the masked [Q, Q] product with Δ·x a head (2·Q²·P), the
    chunk's state a head (2·Q·P·N), the entering state's part of the
    output a head (2·Q·P·N) and one state update between chunks (2·P·N).
    x is read and y written once at [length, heads, head_dim], B and C
    read once at [length, groups, state], Δ once in float32; the decays,
    the [Q, Q] scores and the states need not leave the chip. Backward:
    twice the forward's operations (a product for each operand's
    gradient, nothing recomputed); x, B, C, Δ and dy are read, dx, dB, dC
    and dΔ written. Documents that end inside a row only remove work."""
    Q = chunk
    chunks = rows * -(-length // Q)
    fwd_ops = chunks * (groups * 2 * Q * Q * state + heads * (
        2 * Q * Q * head_dim + 4 * Q * head_dim * state
        + 2 * head_dim * state))
    x_el = rows * length * heads * head_dim
    bc_el = rows * length * groups * state
    dt_bytes = 4 * rows * length * heads
    if not backward:
        return fwd_ops, bytes_per_el * (2 * x_el + 2 * bc_el) + dt_bytes
    return 2 * fwd_ops, bytes_per_el * (3 * x_el + 4 * bc_el) + 2 * dt_bytes


def latent_ffn_cost(rows: float, calls: float, n_groups: int, d: int, f: int,
                    backward: bool, bytes_per_el: int = 2,
                    ) -> Tuple[float, float]:
    """(operations, bytes) of ``calls`` grouped UNGATED expert calls (an
    up and a down GEMM) that together multiply ``rows`` (token, expert)
    rows of the latent width ``d`` through experts of width ``f``, each
    call holding ``n_groups`` experts' weights — ``moe_cost.
    grouped_ffn_cost`` with two matrices an expert where that has three."""
    fwd_ops = 2 * 2 * rows * d * f
    w_el = calls * n_groups * 2 * d * f
    if not backward:
        return fwd_ops, bytes_per_el * (2 * rows * d + w_el)
    return 2 * fwd_ops, bytes_per_el * (3 * rows * d + 2 * w_el)


def layer_counts(cfg: Dict) -> Dict[str, int]:
    """{pattern letter: layers of it} of the configuration as it is run."""
    pattern = cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]
    return {letter: pattern.count(letter) for letter in "ME*"}


def share_params(cfg: Dict) -> int:
    """Parameters one token multiplies through ON THIS SHARE in a forward
    pass — the N of 6·N·T for the cell's utilisation: a Mamba layer's two
    projections, the attention layer's four, an expert layer's router,
    latent projections and shared expert whole and the held part of a
    token's ``num_experts_per_tok`` experts (held / routed of them on
    average), and the sliced head. Norms, the convolution and the scan
    multiply elementwise or against activations and are not counted."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    n = layer_counts(cfg)
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N = cfg["n_groups"], cfg["ssm_state_size"]
    di = H * P
    mamba = d * (2 * di + 2 * G * N + H) + di * d
    nq, nkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    attn = d * nq * dh + 2 * d * nkv * dh + nq * dh * d
    routed = cfg.get("num_routed_experts") or cfg["n_routed_experts"]
    latent = cfg.get("moe_latent_size") or d
    f = cfg["moe_intermediate_size"]
    shared = (cfg.get("n_shared_experts") or 0) * 2 * d * cfg.get(
        "moe_shared_expert_intermediate_size", 0)
    moe = (d * routed + (2 * d * latent if latent != d else 0) + shared
           + cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / routed
           * 2 * latent * f)
    return int(n["M"] * mamba + n["*"] * attn + n["E"] * moe + d * v)
