"""Operation, byte, layer and parameter counts of a GLM-4.7-Flash model
(``model_type`` glm4_moe_lite: multi-head latent attention in every block,
a dense FFN on the leading blocks and a share of an expert layer beside a
shared expert on the others) — kept with the benchmark so that no later PR
that claims a gain can move them (as ``peaks.py``, ``moe_cost.py`` and
``shortconv_cost.py`` keep theirs). Counted from the HF config keys, the
packed grids and the packer's documents, NOT from what implements them.
The grouped expert GEMMs are ``moe_cost.grouped_ffn_cost``. No jax.
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple

from benchmark import peaks


def layer_counts(cfg: Dict) -> Dict[str, int]:
    """{``attn``: blocks (every one has latent attention); ``dense`` |
    ``experts``: blocks of that FFN} of the configuration as it is run."""
    n = cfg["num_hidden_layers"]
    dense = min(int(cfg.get("first_k_dense_replace") or 0), n)
    return {"attn": n, "dense": dense, "experts": n - dense}


def block_runs(cfg: Dict) -> int:
    """Runs of consecutive blocks of one FFN kind: the program scans each
    run and so traces one projection path a run (the cut ``D E E E E`` is
    two)."""
    n = layer_counts(cfg)
    return int(n["dense"] > 0) + int(n["experts"] > 0)


def qk_head_dim(cfg: Dict) -> int:
    return cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]


def geometry(cfg: Dict) -> Tuple[int, ...]:
    """(heads, q_lora_rank, kv_lora_rank, nope, rope, v): what the
    program's trace-time count keys an assembly by, behind rows x length."""
    return (cfg["num_attention_heads"], cfg["q_lora_rank"],
            cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"])


def projection_params(cfg: Dict) -> int:
    """Matrix elements of one block's five projections: q_a, q_b,
    kv_a_with_mqa, kv_b and o_proj (the two latent norms multiply
    elementwise and are not counted)."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return (d * cfg["q_lora_rank"] + cfg["q_lora_rank"] * H * qk_head_dim(cfg)
            + d * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            + cfg["kv_lora_rank"] * H * (cfg["qk_nope_head_dim"]
                                        + cfg["v_head_dim"])
            + H * cfg["v_head_dim"] * d)


def projection_cost(cfg: Dict, tokens: int, backward: bool) -> float:
    """Operations of one block's five projections over ``tokens`` tokens:
    2 a multiply-add forward; backward a product for the input's gradient
    and one for the weight's for each."""
    fwd = 2.0 * tokens * projection_params(cfg)
    return 2 * fwd if backward else fwd


def assemble_cost(cfg: Dict, tokens: int, backward: bool,
                  bytes_per_el: int = 2) -> Tuple[float, float]:
    """(operations, bytes) the ALGORITHM needs between the up-projections
    and the attention call, over ``tokens`` tokens. Forward: ``q_b_proj``'s
    output [H (nope + rope)], ``kv_b_proj``'s [H (nope + v)] and the
    rotary key [rope] are read once; q, k [H (nope + rope)] and v [H v]
    written once — 58 KB a token at 20 heads of 192 + 64 / 256 in
    bfloat16; the rotary key repeated a head, the split halves and the
    turned parts need not leave the chip. RoPE is a multiply, a multiply
    and an add a turned element (H query parts and ONE key part).
    Backward: dq, dk, dv read, the three inputs' gradients written (the
    key part's summed over heads), the turn transposed: the same bytes,
    the same operations and H - 1 adds a rotary dim. The tables are a
    rounding error beside them."""
    H, dr = cfg["num_attention_heads"], cfg["qk_rope_head_dim"]
    qk, nope, dv = qk_head_dim(cfg), cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    read = H * qk + H * (nope + dv) + dr
    write = 2 * H * qk + H * dv
    ops = tokens * 3 * dr * (H + 1)
    if backward:
        ops += tokens * (H - 1) * dr
    return ops, bytes_per_el * tokens * (read + write)


def attention_cost(cfg: Dict, documents: Sequence[int], backward: bool,
                   ) -> Tuple[float, float]:
    """(operations, bytes) of causal attention over a micro-batch's
    DOCUMENTS at the assembled heads (H query and H key heads of nope +
    rope, the value's width equal): ``peaks.flash_attention_cost`` of each
    document alone — the causal half of a document, not of its row."""
    ops = nbytes = 0.0
    for n in documents:
        o, b = peaks.flash_attention_cost(
            1, int(n), cfg["num_attention_heads"],
            cfg["num_key_value_heads"], qk_head_dim(cfg), backward)
        ops, nbytes = ops + o, nbytes + b
    return ops, nbytes


def share_params(cfg: Dict) -> int:
    """Parameters one token multiplies through ON THIS SHARE in a forward
    pass — the N of 6·N·T for the cell's utilisation: every block's five
    projections, the dense blocks' FFN, on each expert block the router,
    the shared experts and the held part of a token's
    ``num_experts_per_tok`` experts (held / routed of them on average),
    and the sliced head. Norms and RoPE multiply elementwise and are not
    counted; the embedding is a lookup; attention's scores are no
    parameter."""
    d, v, fe = cfg["hidden_size"], cfg["vocab_size"], cfg[
        "moe_intermediate_size"]
    n = layer_counts(cfg)
    held = cfg["n_routed_experts"]
    routed = cfg.get("num_routed_experts") or held
    moe = (d * routed + 3 * d * fe * (cfg.get("n_shared_experts") or 0)
           + cfg["num_experts_per_tok"] * held / routed * 3 * d * fe)
    return int(n["attn"] * projection_params(cfg)
               + n["dense"] * 3 * d * cfg["intermediate_size"]
               + n["experts"] * moe + d * v)
