"""Operations and bytes the ALGORITHM of attention under a learned
selection needs (KeyeVL2's ``sa_config``; the program's models/dsa.py),
counted from a micro-batch's DOCUMENTS and the same whatever implements
it — a gather of the selected keys, a sweep of every causal block under a
mask, a recompute of the scores a tile — so an implementation that does
more than the algorithm reads as lost share. No jax.

 - the indexer: three projections a token, and ``2 x Hi x Di`` operations
   a causal same-document pair (one product a head; the ReLU and the
   weighted head sum are not counted), ONE scoring a forward the step
   needs;
 - the selection: one float32 read of every causal same-document score;
 - the attention: ``peaks.flash_attention_cost``'s count at the published
   heads over the SELECTED pairs only, ``sum(min(p + 1, topk))`` a
   document: a full-causal sweep under a mask reads as the fraction it is.
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple


def causal_pairs(documents: Sequence[int]) -> int:
    return sum(int(n) * (int(n) + 1) // 2 for n in documents)


def selected_pairs(documents: Sequence[int], top_k: int) -> int:
    """``sum(min(p + 1, top_k))`` over the positions of the documents."""
    total = 0
    for n in documents:
        n = int(n)
        m = min(n, top_k)
        total += m * (m + 1) // 2 + (n - m) * top_k
    return total


def sa_of(cfg: Dict) -> Tuple[int, int, int]:
    sa = cfg["sa_config"]
    return sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"]


def index_cost(cfg: Dict, documents: Sequence[int]) -> Tuple[float, float]:
    """(operations, bytes) of ONE scoring of a micro-batch: the indexer's
    three projections of every token and a product a head a causal pair;
    bytes: the stream read once, the projections' outputs written and read
    once, the weights once."""
    hi, di, _ = sa_of(cfg)
    d = cfg["hidden_size"]
    tokens = sum(int(n) for n in documents)
    widths = hi * di + di + hi
    ops = 2.0 * tokens * d * widths + 2.0 * hi * di * causal_pairs(documents)
    nbytes = 2.0 * (tokens * d + 2 * tokens * widths + d * widths)
    return ops, nbytes


def select_cost(cfg: Dict, documents: Sequence[int]) -> Tuple[float, float]:
    """(operations, bytes) of ONE selection: every causal same-document
    score read once in float32 (a comparison a score is not counted as an
    operation of the chip's matmul peak)."""
    return 0.0, 4.0 * causal_pairs(documents)


def attention_cost(cfg: Dict, documents: Sequence[int], backward: bool,
                   ) -> Tuple[float, float]:
    """(operations, bytes) of attention over the SELECTED pairs at the
    published heads: QK^T and PV (2 matmuls x 2 flops x head_dim a pair a
    query head), Q, K, V read and O written once; the backward 2.5 x the
    matmul work with Q, K, V, O, dO read and dQ, dK, dV written — as
    ``peaks.flash_attention_cost`` counts a causal call, with the selected
    pairs in the place of L^2 / 2."""
    _, _, top_k = sa_of(cfg)
    H, Hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    tokens = sum(int(n) for n in documents)
    fwd_ops = 2.0 * 2.0 * H * selected_pairs(documents, top_k) * dh
    q_el, kv_el = tokens * H * dh, tokens * Hkv * dh
    if not backward:
        return fwd_ops, 2.0 * (2 * q_el + 2 * kv_el)
    return 2.5 * fwd_ops, 2.0 * (4 * q_el + 4 * kv_el + q_el)


def share_params(cfg: Dict) -> int:
    """Parameters of the cut the configuration file describes."""
    hi, di, _ = sa_of(cfg)
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    attn = d * dh * (2 * H + 2 * Hkv) + 2 * dh
    indexer = d * (hi * di + di + hi) + 2 * di
    routed = cfg.get("num_routed_experts") or cfg["num_experts"]
    experts = cfg["num_experts"] * 3 * d * cfg["moe_intermediate_size"]
    block = attn + indexer + d * routed + 2 * d + experts
    return (cfg["num_hidden_layers"] * block + 2 * cfg["vocab_size"] * d + d)
