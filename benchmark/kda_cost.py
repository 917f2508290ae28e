"""Operation, byte, layer and parameter counts of a Kimi-Linear model
(``model_type`` kimi_linear: whole blocks of a Kimi Delta Attention mixer
or of un-rotated latent attention with a value head narrower than its key,
a dense FFN on the leading block and an expert layer on the others) — kept
with the benchmark so that no later PR that claims a gain can move them (as
``peaks.py``, ``gdn_cost.py`` and ``mla_cost.py`` keep theirs). Counted
from the HF config keys and the packed grids, NOT from what implements
them. The grouped expert GEMMs are ``moe_cost.grouped_ffn_cost``. No jax.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

from benchmark import peaks

CHUNK = 64  # the chunk the rule's counts are stated for (fla's)


def held_layers(cfg: Dict) -> List[int]:
    """The published (1-based) numbers of the blocks the configuration
    runs."""
    n = cfg["num_hidden_layers"]
    return list(cfg.get("held_layers") or range(1, n + 1))[:n]


def block_kinds(cfg: Dict) -> List[Tuple[bool, bool]]:
    """[(mixes with KDA, its FFN is the dense MLP)] in layer order."""
    kda_at = set(cfg["linear_attn_config"]["kda_layers"])
    dense = int(cfg.get("first_k_dense_replace") or 0)
    return [(i in kda_at, i <= dense) for i in held_layers(cfg)]


def layer_counts(cfg: Dict) -> Dict[str, int]:
    """{``kda`` | ``attn``: blocks of that mixer; ``dense`` | ``experts``:
    blocks of that FFN} of the configuration as it is run."""
    kinds = block_kinds(cfg)
    return {"kda": sum(k for k, _ in kinds),
            "attn": sum(not k for k, _ in kinds),
            "dense": sum(d for _, d in kinds),
            "experts": sum(not d for _, d in kinds)}


def runs(cfg: Dict, kda: bool) -> int:
    """Runs of consecutive blocks of one kind (mixer and FFN) whose mixer
    is KDA (``kda``) or latent attention: the program scans each run and
    so traces one rule, or one assembly, a run (the cut ``D K K K A``: two
    and one)."""
    kinds = block_kinds(cfg)
    return sum(k[0] == kda and (i == 0 or kinds[i - 1] != k)
               for i, k in enumerate(kinds))


def rule_geometry(cfg: Dict) -> Tuple[int, int, int, int]:
    """(chunk, heads, head width, gate rank): what the program's
    trace-time count keys a rule by, behind rows x length."""
    lin = cfg["linear_attn_config"]
    return (CHUNK, lin["num_heads"], lin["head_dim"], lin["head_dim"])


def mla_geometry(cfg: Dict) -> Tuple[int, ...]:
    """(heads, q latent — 0: none —, kv latent, nope, rope, v): what the
    program's trace-time count keys an assembly by."""
    return (cfg["num_attention_heads"], cfg.get("q_lora_rank") or 0,
            cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"])


def kda_rule_cost(rows: int, length: int, heads: int, dk: int, dv: int,
                  backward: bool, chunk: int = CHUNK,
                  bytes_per_el: int = 2) -> Tuple[float, float]:
    """(operations, bytes) the ALGORITHM needs for one delta rule with a
    decay a key channel over a packed [rows, length] grid in chunks of Q
    tokens. A chunk a head: k kᵀ and q kᵀ under the decays (2 x 2 Q² dk:
    the decay a channel scales the operands, it adds no product), the unit
    lower-triangular system solved for [β v | β e^c k] by substitution (Q²
    (dk + dv): half a product, no inverse is formed), the triangular q kᵀ
    block against the chunk's deltas (Q² dv), and three products against
    the carried state (3 x 2 Q dk dv). q, k and v are read and o written
    once at [length, heads, 128]; g once at [length, heads, dk] in float32
    (a decay a CHANNEL: as many elements as k) and β once in float32; the
    [Q, Q] blocks and the states need not leave the chip. Backward: twice
    the forward's operations; q, k, v, g, β and do are read, dq, dk, dv,
    dg and dβ written. The same count whatever implements the rule."""
    Q = chunk
    chunks = rows * -(-length // Q)
    fwd_ops = chunks * heads * (4 * Q * Q * dk + Q * Q * (dk + dv)
                                + Q * Q * dv + 6 * Q * dk * dv)
    k_el = rows * length * heads * dk
    v_el = rows * length * heads * dv
    gate_bytes = 4 * (k_el + rows * length * heads)
    if not backward:
        return fwd_ops, bytes_per_el * (2 * k_el + 2 * v_el) + gate_bytes
    return 2 * fwd_ops, bytes_per_el * (4 * k_el + 4 * v_el) + 2 * gate_bytes


def attention_cost(cfg: Dict, documents: Sequence[int], backward: bool,
                   ) -> Tuple[float, float]:
    """(operations, bytes) of causal attention over a micro-batch's
    DOCUMENTS at the PUBLISHED heads — q kᵀ at ``qk_nope_head_dim +
    qk_rope_head_dim`` (192), p v at ``v_head_dim`` (128):
    ``peaks.flash_attention_cost`` of each document alone, half of it at
    either width (it is linear in the width) — so lanes that an
    implementation multiplies as padding read as lost share."""
    H = cfg["num_attention_heads"]
    ops = nbytes = 0.0
    for n in documents:
        for width in (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"]):
            o, b = peaks.flash_attention_cost(
                1, int(n), H, cfg["num_key_value_heads"], width, backward)
            ops, nbytes = ops + o / 2, nbytes + b / 2
    return ops, nbytes


def kda_params(cfg: Dict) -> int:
    """Matrix elements of one KDA mixer: the three projections, β's, the
    two gates' bottlenecks and expansions, the out-projection."""
    d, lin = cfg["hidden_size"], cfg["linear_attn_config"]
    width, rank = lin["num_heads"] * lin["head_dim"], lin["head_dim"]
    return (3 * d * width + d * lin["num_heads"] + 2 * d * rank
            + 2 * rank * width + width * d)


def mla_params(cfg: Dict) -> int:
    """Matrix elements of one latent-attention branch without a query
    latent: q, kv_a_with_mqa, kv_b and o_proj."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (d * H * qk + d * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            + cfg["kv_lora_rank"] * H * (cfg["qk_nope_head_dim"]
                                        + cfg["v_head_dim"])
            + H * cfg["v_head_dim"] * d)


def share_params(cfg: Dict) -> int:
    """Parameters one token multiplies through ON THIS SHARE in a forward
    pass — the N of 6·N·T for the cell's utilisation: each mixer's
    matrices, the dense block's FFN, on each expert block the router, the
    shared expert and the held part of a token's ``num_experts_per_token``
    experts (held / routed of them on average), and the sliced head.
    Norms, the convolutions and the rule multiply elementwise or against
    activations and are not counted; the embedding is a lookup."""
    d, v, fe = cfg["hidden_size"], cfg["vocab_size"], cfg[
        "moe_intermediate_size"]
    n = layer_counts(cfg)
    held = cfg["num_experts"]
    routed = cfg.get("num_routed_experts") or held
    moe = (d * routed + 3 * d * fe * (cfg.get("num_shared_experts") or 0)
           + cfg["num_experts_per_token"] * held / routed * 3 * d * fe)
    return int(n["kda"] * kda_params(cfg) + n["attn"] * mla_params(cfg)
               + n["dense"] * 3 * d * cfg["intermediate_size"]
               + n["experts"] * moe + d * v)
