"""The yardstick's table of chip peaks and its operation / byte counts.

Kept with the benchmark so that no later PR that claims a gain can move
them. Arithmetic copied from ``areal_tpu/base/monitor.py``
(``train_flops_6nt``, ``device_peak_flops``), not imported.
"""

from __future__ import annotations

from typing import Dict, Tuple

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s per chip. jax reports the chip as "TPU v5 lite".
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
    "TPU v5e": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9},
}


def peak(device_kind: str) -> Dict[str, float]:
    """Peaks of one chip; an unknown kind is an error, never a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks on record for device kind {device_kind!r} "
                       f"(have {sorted(PEAKS)})")
    return PEAKS[device_kind]


def param_count(cfg: Dict) -> int:
    """Parameters of a dense GQA transformer from its HF config keys (all
    activated). Tied embeddings are counted once."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg.get("head_dim") or d // nq
    qd, kvd = nq * dh, nkv * dh
    per_layer = (d * qd + 2 * d * kvd + qd * d      # wq wk wv wo
                 + (qd + 2 * kvd)                   # qkv bias (qwen2)
                 + 3 * d * f                        # gated MLP
                 + 2 * d)                           # two norms
    head = 0 if cfg.get("tie_word_embeddings") else d * v
    return cfg["num_hidden_layers"] * per_layer + v * d + d + head


def train_flops_6nt(n_params: float, n_tokens: float) -> float:
    """6·N·T: forward 2·N·T + backward 4·N·T; recompute is not counted."""
    return 6.0 * float(n_params) * float(n_tokens)


def flash_attention_cost(rows: int, length: int, n_q_heads: int,
                         n_kv_heads: int, head_dim: int, backward: bool,
                         bytes_per_el: int = 2) -> Tuple[float, float]:
    """(operations, bytes) the ALGORITHM needs for one causal attention
    call over a packed [rows, length] grid: QK^T and PV over the causal
    half (2 matmuls x 2 flops x L^2/2 x Dh per head), each of Q, K, V read
    once and O written once at the published head sizes (K/V at the
    n_kv_heads they have, not repeated, head_dim not lane-padded). The
    backward pass needs 2.5x the forward's matmul work (dQ, dK, dV and the
    recomputed scores) and reads Q, K, V, O, dO and writes dQ, dK, dV.
    Block-causal masking inside a packed row only removes work, so this is
    an upper bound on the needed operations and the share a lower bound
    on none: rows hold several documents, so it is stated as it is."""
    fwd_ops = 2 * 2 * rows * n_q_heads * (length * length / 2) * head_dim
    q_el = rows * length * n_q_heads * head_dim
    kv_el = rows * length * n_kv_heads * head_dim
    if not backward:
        return fwd_ops, bytes_per_el * (2 * q_el + 2 * kv_el)
    return 2.5 * fwd_ops, bytes_per_el * (4 * q_el + 4 * kv_el + q_el)


def least_time(ops: float, nbytes: float, device_kind: str,
               ) -> Tuple[float, str]:
    """The least seconds the chip could take, and which peak binds."""
    p = peak(device_kind)
    t_ops, t_bytes = ops / p["flops_bf16"], nbytes / p["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
