"""Operation, byte, layer and parameter counts of an LFM2-MoE model
(``model_type`` lfm2_moe: whole blocks of a doubly gated short convolution
or GQA at heads of 64, a dense FFN on the leading blocks and a share of an
expert layer on the others) — kept with the benchmark so that no later PR
that claims a gain can move them (as ``peaks.py``, ``moe_cost.py`` and
``gdn_cost.py`` keep theirs). Counted from the HF config keys, the packed
grids and the packer's documents, NOT from what implements them. The
grouped expert GEMMs are ``moe_cost.grouped_ffn_cost``. No jax.
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple

from benchmark import peaks


def layer_types(cfg: Dict) -> Sequence[str]:
    return cfg["layer_types"][:cfg["num_hidden_layers"]]


def layer_counts(cfg: Dict) -> Dict[str, int]:
    """{``conv`` | ``full``: blocks of that mixer; ``dense`` | ``experts``:
    blocks of that FFN} of the configuration as it is run."""
    types = layer_types(cfg)
    dense = min(int(cfg.get("num_dense_layers") or 0), len(types))
    return {"conv": sum(t == "conv" for t in types),
            "full": sum(t == "full_attention" for t in types),
            "dense": dense, "experts": len(types) - dense}


def conv_runs(cfg: Dict) -> int:
    """Runs of consecutive short-convolution blocks of one FFN kind in the
    configuration as it is run: the program scans each run and so traces
    one convolution a run (the cut ``c(dense) A c c c`` is two)."""
    dense = int(cfg.get("num_dense_layers") or 0)
    kinds = [(t, i < dense) for i, t in enumerate(layer_types(cfg))]
    return sum(k[0] == "conv" and (i == 0 or kinds[i - 1] != k)
               for i, k in enumerate(kinds))


def glue_cost(rows: int, length: int, channels: int, taps: int,
              backward: bool, bytes_per_el: int = 2) -> Tuple[float, float]:
    """(operations, bytes) the ALGORITHM needs for one doubly gated short
    convolution between its two projections, over a packed [rows, length]
    grid. Forward: ``B ⊙ x`` (1), ``taps`` products and ``taps - 1`` sums,
    ``C ⊙`` (1) a channel a token; the in-projection's output [3 C] is
    read once and ``y`` [C] written once — 16 KB a token at 2048 channels
    in bfloat16; the shifted copies and the products need not leave the
    chip. Backward: ``dy`` [C] and the in-projection's output [3 C] are
    read, its gradient [3 C] written; ``z`` and ``c`` are rebuilt (the
    forward's operations), then ``dC``, ``dc``, the transposed taps,
    ``dB``, ``dx`` and the taps' own gradient (a product and a sum a tap).
    The taps' weights and the segment ids are a rounding error beside
    them. Documents that end inside a row only remove work."""
    tokens = rows * length
    fwd_ops = tokens * channels * (2 * taps + 1)
    if not backward:
        return fwd_ops, bytes_per_el * tokens * 4 * channels
    return (fwd_ops + tokens * channels * (4 * taps + 3),
            bytes_per_el * tokens * 7 * channels)


def projection_cost(tokens: int, channels: int) -> float:
    """Operations of a mixer's two projections, forward: [D, 3 D] and
    [D, D]."""
    return 2.0 * tokens * channels * 4 * channels


def head_dim(cfg: Dict) -> int:
    return cfg.get("head_dim") or (cfg["hidden_size"]
                                   // cfg["num_attention_heads"])


def attention_cost(cfg: Dict, documents: Sequence[int], backward: bool,
                   ) -> Tuple[float, float]:
    """(operations, bytes) of causal attention over a micro-batch's
    DOCUMENTS at the published heads (32 query / 8 key-value heads of 64):
    ``peaks.flash_attention_cost`` of each document alone — the causal
    half of a document, not of its row: a row of this mix holds 3 to 9
    documents, whose cross terms no kernel needs."""
    ops = nbytes = 0.0
    for n in documents:
        o, b = peaks.flash_attention_cost(
            1, int(n), cfg["num_attention_heads"],
            cfg["num_key_value_heads"], head_dim(cfg), backward)
        ops, nbytes = ops + o, nbytes + b
    return ops, nbytes


def share_params(cfg: Dict) -> int:
    """Parameters one token multiplies through ON THIS SHARE in a forward
    pass — the N of 6·N·T for the cell's utilisation: a short-convolution
    block's two projections, an attention block's four, the dense blocks'
    FFN, on each expert block the router and the held part of a token's
    ``num_experts_per_tok`` experts (held / routed of them on average),
    and the head (the embedding's transpose, sliced). Norms, the gates
    and the taps multiply elementwise and are not counted; the embedding
    is a lookup."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    nq, nkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   head_dim(cfg))
    n = layer_counts(cfg)
    routed = cfg.get("num_routed_experts") or cfg["num_experts"]
    attn = d * (nq * dh + 2 * nkv * dh) + nq * dh * d
    moe = (d * routed + cfg["num_experts_per_tok"] * cfg["num_experts"]
           / routed * 3 * d * cfg["moe_intermediate_size"])
    return int(n["conv"] * 4 * d * d + n["full"] * attn
               + n["dense"] * 3 * d * cfg["intermediate_size"]
               + n["experts"] * moe + d * v)
