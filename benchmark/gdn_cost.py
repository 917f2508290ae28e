"""Operation, byte, layer and parameter counts of a Qwen3-Next model
(``model_type`` qwen3_next: whole blocks of a Gated DeltaNet mixer or
gated softmax attention at heads of 256, each with an expert layer) — kept
with the benchmark so that no later PR that claims a gain can move them
(as ``peaks.py``, ``moe_cost.py`` and ``ssm_cost.py`` keep theirs).
Counted from the HF config keys and the packed grids, NOT from what
implements them. The grouped expert GEMMs are ``moe_cost.grouped_ffn_cost``.
No jax.
"""

from __future__ import annotations

from typing import Dict, Tuple

from benchmark import peaks

CHUNK = 64  # the chunk the counts below are stated for (fla's, HF's)


def is_full(cfg: Dict, layer: int) -> bool:
    published = int(cfg.get("first_layer_index", 0)) + layer
    return (published + 1) % cfg["full_attention_interval"] == 0


def layer_counts(cfg: Dict) -> Dict[str, int]:
    """{``gdn`` | ``full``: layers of it} of the configuration as it is
    run."""
    full = sum(is_full(cfg, i) for i in range(cfg["num_hidden_layers"]))
    return {"gdn": cfg["num_hidden_layers"] - full, "full": full}


def gdn_runs(cfg: Dict) -> int:
    """Runs of consecutive Gated DeltaNet layers in the configuration as it
    is run: the program scans each run and so traces one rule a run."""
    n = cfg["num_hidden_layers"]
    return sum(not is_full(cfg, i) and (i == 0 or is_full(cfg, i - 1))
               for i in range(n))


def gdn_rule_cost(rows: int, length: int, k_heads: int, v_heads: int,
                  dk: int, dv: int, backward: bool, chunk: int = CHUNK,
                  bytes_per_el: int = 2) -> Tuple[float, float]:
    """(operations, bytes) the ALGORITHM needs for one gated delta rule
    over a packed [rows, length] grid in chunks of Q tokens. A chunk a KEY
    head: k k^T and q k^T (2 x 2 Q² dk). A chunk a VALUE head: the unit
    lower-triangular system solved for [beta v | beta e^c k] by
    substitution (Q² (dk + dv): half a product, no inverse is formed),
    the triangular q k^T block against the chunk's deltas (Q² dv), and
    three products against the carried state — what the state predicts, what
    it adds to the output, and its update (3 x 2 Q dk dv). q and k are
    read once at [length, k_heads, dk], v read and o written once at
    [length, v_heads, dv], g and beta once in float32; the [Q, Q] blocks
    and the states need not leave the chip. Backward: twice the forward's
    operations (a product for each operand's gradient, nothing
    recomputed); q, k, v, g, beta and do are read, dq, dk, dv, dg and
    dbeta written. Documents that end inside a row only remove work."""
    Q = chunk
    chunks = rows * -(-length // Q)
    fwd_ops = chunks * (k_heads * 4 * Q * Q * dk + v_heads * (
        Q * Q * (dk + dv) + Q * Q * dv + 6 * Q * dk * dv))
    qk_el = rows * length * k_heads * dk
    v_el = rows * length * v_heads * dv
    gate_bytes = 2 * 4 * rows * length * v_heads
    if not backward:
        return fwd_ops, bytes_per_el * (2 * qk_el + 2 * v_el) + gate_bytes
    return 2 * fwd_ops, bytes_per_el * (4 * qk_el + 4 * v_el) + 2 * gate_bytes


def attention_cost(cfg: Dict, rows: int, length: int, backward: bool,
                   ) -> Tuple[float, float]:
    """(operations, bytes) of one causal attention call at the published
    heads (16 query / 2 key-value heads of 256: ``peaks.
    flash_attention_cost``, which counts the causal half and every tensor
    once, K/V at the heads they have and no lane padding)."""
    return peaks.flash_attention_cost(
        rows, length, cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["head_dim"], backward)


def share_params(cfg: Dict) -> int:
    """Parameters one token multiplies through ON THIS SHARE in a forward
    pass — the N of 6·N·T for the cell's utilisation: a Gated DeltaNet
    block's three projections, an attention block's five (the gate's
    among them), in every block the router, the shared expert with its
    gate and the held part of a token's ``num_experts_per_tok`` experts
    (held / routed of them on average), and the sliced head. Norms, the
    convolution and the rule multiply elementwise or against activations
    and are not counted; the embedding is a lookup."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    G, H = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    gdn = d * (2 * G * dk + 2 * H * dv + 2 * H) + H * dv * d
    nq, nkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    attn = d * (2 * nq * dh + 2 * nkv * dh) + nq * dh * d
    routed = cfg.get("num_routed_experts") or cfg["num_experts"]
    fe, fs = cfg["moe_intermediate_size"], cfg[
        "shared_expert_intermediate_size"]
    moe = (d * routed + 3 * d * fs + d
           + cfg["num_experts_per_tok"] * cfg["num_experts"] / routed
           * 3 * d * fe)
    n = layer_counts(cfg)
    return int(n["gdn"] * gdn + n["full"] * attn
               + cfg["num_hidden_layers"] * moe + d * v)
