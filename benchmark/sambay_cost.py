"""Operation, byte and parameter counts of a decoder-hybrid-decoder
model's own layers (``model_type`` phi4flash) — the selective scan (S6,
Mamba-1) — kept with the benchmark so that no later PR that claims a gain
can move them (as ``peaks.py``, ``moe_cost.py`` and ``ssm_cost.py`` keep
theirs). From the HF config keys; no jax.
"""

from __future__ import annotations

from typing import Dict, Tuple

LETTERS = "MSFGX"


def pattern(cfg: Dict) -> str:
    """A letter a layer of the configuration as it is run: its
    ``layer_pattern`` (a cut in depth), else the published rule."""
    n = cfg["num_hidden_layers"]
    if cfg.get("layer_pattern"):
        return cfg["layer_pattern"][:n]
    every = cfg["mb_per_layer"]
    return "".join(
        ("M" if i <= n // 2 else "G") if i % every == 0
        else "S" if i < n // 2 else "F" if i == n // 2 + 1 else "X"
        for i in range(n))


def layer_counts(cfg: Dict) -> Dict[str, int]:
    p = pattern(cfg)
    return {letter: p.count(letter) for letter in LETTERS}


def s6_sizes(cfg: Dict) -> Tuple[int, int, int]:
    """(d_inner, states, dt_rank): Mamba-1's defaults, which have no key."""
    d = cfg["hidden_size"]
    return 2 * d, 16, -(-d // 16)


def selective_scan_cost(rows: int, length: int, d_inner: int, state: int,
                        backward: bool, bytes_per_el: int = 2,
                        ) -> Tuple[float, float]:
    """(operations, bytes) the ALGORITHM needs for one selective scan over
    a packed [rows, length] grid. Forward, per token, channel and state:
    the decay's exp, two multiplies (decay x state, and Δx x B) and an add
    — the recurrence — and the multiply-add of y's contraction with C: 6
    operations on ``d_inner x state`` values a token. x is read and y
    written once at [length, d_inner], Δ read once in float32, B and C
    once at [length, state]; the state need not leave the chip. Backward
    (the reverse recurrence alone: the forward it re-runs is counted as a
    forward call): the gradient's decay and the gate's product (2), the
    decay's gradient (2 multiplies), dA's multiply-add (2), dΔ's
    contraction with A (2), dx's with B (2), dB's and dC's (4): 14; x, Δ,
    B, C and dy are read, dx, dΔ, dB and dC written. Documents that end
    inside a row only remove work."""
    el = rows * length * d_inner
    bc = rows * length * state
    if not backward:
        return 6.0 * el * state, bytes_per_el * (2 * el + 2 * bc) + 4 * el
    return 14.0 * el * state, bytes_per_el * (3 * el + 4 * bc) + 2 * 4 * el


def share_params(cfg: Dict) -> int:
    """Parameters one token multiplies through ON THIS CUT in a forward
    pass — the N of 6·N·T for the cell's utilisation: the matrices of
    every block by its letter (a Mamba block's four projections, attention's
    four or a cross layer's two, a gated memory unit's two, the gated MLP's
    three) and the sliced head. Norms, biases, the convolution, the scan,
    lambda's vectors and the sub-norm multiply elementwise or against
    activations and are not counted."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = d // nq
    di, N, r = s6_sizes(cfg)
    mixer = {
        "M": d * 2 * di + di * (r + 2 * N) + r * di + di * d,
        "S": 2 * d * nq * dh + 2 * d * nkv * dh,
        "G": 2 * d * di,
        "X": 2 * d * nq * dh,
    }
    mixer["F"] = mixer["S"]
    n = layer_counts(cfg)
    return int(sum(n[c] * (mixer[c] + 3 * d * f) for c in LETTERS) + d * v)
