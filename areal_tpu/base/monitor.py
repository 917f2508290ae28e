"""Analytic FLOPs / MFU accounting and experiment metric writers.

Parity target: ``realhf/base/monitor.py:288-330`` (llama-family analytic
FLOPs formulas feeding TFLOPs/GPU master logs) + the master's
wandb/swanlab/tensorboard init (``realhf/system/master_worker.py:291-350``)
+ ``realhf/system/flops_counter.py`` (per-MFC FLOPs sums). TPU differences:
peak-FLOPs table is per TPU generation (bf16), and the writers degrade
gracefully to tensorboard-only (wandb is optional on pods).
"""

from __future__ import annotations

import json
import math
from typing import Any, Dict, Optional

# bf16 peak FLOP/s of one chip, keyed by the EXACT ``device_kind`` jax
# reports (a v5e reports "TPU v5 lite", a v5p "TPU v5"; the second
# spelling of each is the one jax's own tpu_info also accepts). Source:
# Google Cloud TPU documentation, system architecture page per generation.
TPU_PEAK_BF16 = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12, "TPU v5e": 197e12,
    "TPU v5": 459e12, "TPU v5p": 459e12,
    "TPU v6 lite": 918e12, "TPU v6e": 918e12,
}


def device_peak_flops(device_kind: Optional[str] = None) -> Optional[float]:
    """Peak bf16 FLOP/s of one chip of ``device_kind`` (default: this
    process's first device). A TPU kind that is not in the table is an
    error — a silent miss turned MFU into an absent or zero number; any
    other device (the CPU) has no peak: None."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    if device_kind in TPU_PEAK_BF16:
        return TPU_PEAK_BF16[device_kind]
    if device_kind.startswith("TPU"):
        raise KeyError(
            f"no bf16 peak on record for device kind {device_kind!r}; add "
            f"it to base/monitor.TPU_PEAK_BF16 (have "
            f"{sorted(TPU_PEAK_BF16)})"
        )
    return None


def device_report() -> Dict[str, Any]:
    """What this process's JAX runtime got, as jax reports it: platform,
    ``device_kind``, global device count, and per local device its id,
    coordinates and allocator counters (None where the backend has no
    ``memory_stats``, e.g. the CPU). Initializes the backend — only for
    processes that own their devices."""
    import jax

    local = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        local.append({
            "id": d.id,
            "coords": list(getattr(d, "coords", ()) or ()) or None,
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        })
    first = jax.devices()[0]
    return {
        "platform": first.platform,
        "device_kind": first.device_kind,
        "device_count": jax.device_count(),
        "local_devices": local,
    }


DEVICE_REPORT_TAG = "device_report "


def log_device_report(logger, worker: str, **extra: Any) -> None:
    """One machine-readable log line per device-owning worker
    (``device_report {json}``): chip_smoke.py — which never imports jax —
    reads the device and the counters from it."""
    logger.info(DEVICE_REPORT_TAG + json.dumps(
        {"worker": worker, **device_report(), **extra}
    ))


def transformer_flops_per_token(
    n_layers: int,
    hidden_dim: int,
    q_dim: int,
    kv_dim: int,
    intermediate_dim: int,
    vocab_size: int,
    avg_seqlen: float,
    backward: bool = True,
    remat: bool = False,
    moe=None,
) -> float:
    """Analytic FLOPs per token (llama formula family, reference
    monitor.py:288-330): matmul terms 2·m·n·k plus the attention-score
    quadratic term; backward ≈ 2× forward, or 3× forward under activation
    rematerialization (the forward is recomputed in the backward pass —
    reference checkpoint_activations_factor=4).

    ``moe`` (a models.config.MoEConfig or anything with its fields)
    switches the MLP term to ACTIVATED compute: each token runs top_k
    routed experts plus the router matmul plus the always-on shared
    expert — not all num_experts — so MoE MFU is measured against the
    FLOPs the token actually buys, matching activated_param_count
    (models/transformer.py)."""
    d, f = hidden_dim, intermediate_dim
    attn_proj = 2 * d * (q_dim + 2 * kv_dim) + 2 * q_dim * d
    attn_score = 2 * 2 * q_dim * avg_seqlen  # QK^T and PV, causal avg ≈ L/2·2
    if moe is not None:
        fr = moe.routed_intermediate_dim or f
        # on a share of the layer: the router scores all the published
        # experts, and held / routed of a token's top_k run here
        routed = getattr(moe, "n_routed", moe.num_experts)
        mlp = (moe.top_k * moe.num_experts / routed * 3 * 2 * d * fr
               + 2 * d * routed)
        if moe.shared_intermediate_dim:
            mlp += 3 * 2 * d * moe.shared_intermediate_dim
    else:
        mlp = 3 * 2 * d * f
    per_layer = attn_proj + attn_score + mlp
    head = 2 * d * vocab_size
    fwd = n_layers * per_layer + head
    if not backward:
        return fwd
    return fwd * (4.0 if remat else 3.0)


def train_flops_6nt(n_params: float, n_tokens: float) -> float:
    """The classic ``6·N·T`` train-FLOPs estimate (fwd 2·N·T + bwd 4·N·T)
    over parameter count alone. Coarser than
    :func:`model_flops_per_token` (no attention quadratic term, no remat
    factor) but geometry-free, which is what a number compared across
    packings wants; both live HERE so every caller shares one accounting
    (no duplicated formulas to drift apart)."""
    return 6.0 * float(n_params) * float(n_tokens)


def model_flops_per_token(
    cfg, avg_seqlen: float, backward: bool = True, remat: bool = False
) -> float:
    """FLOPs/token from a models.config.TransformerConfig. A Gated
    DeltaNet block's mixer is counted in place of attention's: its three
    projections, and the rule in chunks of Q tokens a value head — the two
    [Q, Q] products and the inverse's 2 log2(Q) - 1 of them inside a
    chunk, the two triangular applications and the five products against
    the carried state; a short-convolution block's likewise
    (:func:`_shortconv_flops`), and latent attention's five projections in
    place of q/k/v/o (:func:`_mla_flops`)."""
    flops = transformer_flops_per_token(
        cfg.n_layers, cfg.hidden_dim, cfg.q_dim, cfg.kv_dim,
        cfg.intermediate_dim, 1 if cfg.is_critic else cfg.vocab_size,
        avg_seqlen, backward=backward, remat=remat,
        moe=getattr(cfg, "moe", None),
    )
    factor = 1.0 if not backward else 4.0 if remat else 3.0
    flops += (_shortconv_flops(cfg, avg_seqlen) + _mla_flops(cfg)
              + _kda_flops(cfg, avg_seqlen)) * factor
    gdn = getattr(cfg, "gdn", None)
    n_gdn = cfg.layer_kinds.count("gdn") if gdn is not None else 0
    if not n_gdn:
        return flops
    d, Q = cfg.hidden_dim, gdn.chunk_size
    dk, dv = gdn.k_head_dim, gdn.v_head_dim
    attention = (2 * d * (cfg.q_dim + 2 * cfg.kv_dim) + 2 * cfg.q_dim * d
                 + 2 * 2 * cfg.q_dim * avg_seqlen)
    rule = (gdn.n_k_heads * 2 * 2 * Q * dk + gdn.n_v_heads * 2 * (
        (2 * math.log2(Q) - 1) * Q * Q + Q * (dk + dv) + dk * dv * 3
        + Q * dv))
    mixer = (2 * d * (gdn.qkvz_dim + gdn.ba_dim) + 2 * gdn.value_dim * d
             + rule)
    return flops + n_gdn * (mixer - attention) * factor


def _shortconv_flops(cfg, avg_seqlen: float) -> float:
    """What a model with short-convolution blocks (``cfg.shortconv``)
    differs by from the count above, a token's forward pass: each such
    block's mixer — ``[B | C | x]`` and the out-projection, two gates and
    K taps a channel — in place of attention's, and on each of its blocks
    that run the dense MLP (the leading ones) that MLP in place of the
    routed experts. 0 for any other model."""
    sc = getattr(cfg, "shortconv", None)
    if sc is None:
        return 0.0
    from areal_tpu.models.config import CONV, attention_kind

    d = cfg.hidden_dim
    attention = (2 * d * (cfg.q_dim + 2 * cfg.kv_dim) + 2 * cfg.q_dim * d
                 + 2 * 2 * cfg.q_dim * avg_seqlen)
    mixer = 2 * d * 3 * d + 2 * d * d + (2 + 2 * sc.kernel) * d
    n_conv = sum(attention_kind(k) == CONV for k in cfg.layer_kinds)
    return n_conv * (mixer - attention) + _dense_block_flops(cfg)


def _dense_block_flops(cfg) -> float:
    """What the blocks that run the dense MLP in a model with experts (the
    leading ones) differ by from the count above, a token's forward pass:
    that MLP in place of the routed experts, the router and the shared
    expert. 0 where every block runs the experts."""
    from areal_tpu.models.config import has_dense_ffn

    moe, d = cfg.moe, cfg.hidden_dim
    if moe is None:
        return 0.0
    experts = (moe.top_k * moe.num_experts / moe.n_routed * 3 * 2 * d
               * (moe.routed_intermediate_dim or cfg.intermediate_dim)
               + 2 * d * moe.n_routed
               + 3 * 2 * d * (moe.shared_intermediate_dim or 0))
    n_dense = sum(map(has_dense_ffn, cfg.layer_kinds))
    return n_dense * (3 * 2 * d * cfg.intermediate_dim - experts)


def _mla_flops(cfg) -> float:
    """What a model with latent attention (``cfg.mla``) differs by from
    the count above, a token's forward pass: every block's five
    projections (both latents, both expansions, o_proj) in place of
    q/k/v/o — the attention proper is counted as any block's, at
    ``q_dim`` — and its leading dense blocks' MLP
    (:func:`_dense_block_flops`). 0 for any other model."""
    mla = getattr(cfg, "mla", None)
    if mla is None:
        return 0.0
    from areal_tpu.models.mla import flops_per_token

    d = cfg.hidden_dim
    qkvo = 2 * d * (cfg.q_dim + 2 * cfg.kv_dim) + 2 * cfg.q_dim * d
    n_attn = sum(cfg.attention_windows().values())
    return (n_attn * (flops_per_token(mla, d, cfg.n_q_heads) - qkvo)
            + _dense_block_flops(cfg))


def _kda_flops(cfg, avg_seqlen: float) -> float:
    """What a model with Kimi Delta Attention blocks (``cfg.kda``) differs
    by from the count above, a token's forward pass: each such block's
    mixer — its matrices, and the rule in chunks of Q tokens a head as
    :func:`model_flops_per_token` counts a Gated DeltaNet's — in place of
    attention's. 0 for any other model."""
    kda = getattr(cfg, "kda", None)
    if kda is None:
        return 0.0
    from areal_tpu.models.config import KDA, attention_kind
    from areal_tpu.models.kda import kda_param_count

    d, Q, dh = cfg.hidden_dim, kda.chunk_size, kda.head_dim
    attention = (2 * d * (cfg.q_dim + 2 * cfg.kv_dim) + 2 * cfg.q_dim * d
                 + 2 * 2 * cfg.q_dim * avg_seqlen)
    rule = kda.n_heads * 2 * (2 * Q * dh + (2 * math.log2(Q) - 1) * Q * Q
                              + 2 * Q * dh + 3 * dh * dh + Q * dh)
    n_kda = sum(attention_kind(k) == KDA for k in cfg.layer_kinds)
    return n_kda * (2 * kda_param_count(kda, d) + rule - attention)


class FlopsCounter:
    """Per-step FLOPs sum over MFCs (reference flops_counter.py:15)."""

    def __init__(self):
        self.flops = 0.0

    def add_train(
        self, cfg, n_tokens: float, avg_seqlen: float, remat: bool = False
    ) -> None:
        self.flops += (
            model_flops_per_token(cfg, avg_seqlen, True, remat=remat)
            * n_tokens
        )

    def add_inf(self, cfg, n_tokens: float, avg_seqlen: float) -> None:
        self.flops += model_flops_per_token(cfg, avg_seqlen, False) * n_tokens

    def pop(self) -> float:
        f, self.flops = self.flops, 0.0
        return f


class MetricWriter:
    """Tensorboard (+ optional wandb) scalar writer for the master loop."""

    def __init__(self, tensorboard_path: Optional[str] = None,
                 wandb_mode: str = "disabled", wandb_kwargs=None):
        import threading

        # The telemetry aggregator's ingest thread mirrors worker scalars
        # into the same writer the master loop uses — SummaryWriter is not
        # thread-safe, so writes serialize (same fix class as the PR 3
        # evaluator writer lock).
        self._lock = threading.Lock()
        self._tb = None
        self._wandb = None
        if tensorboard_path:
            try:
                from torch.utils.tensorboard import SummaryWriter

                self._tb = SummaryWriter(log_dir=tensorboard_path)
            except Exception:  # pragma: no cover - tb optional
                pass
        if wandb_mode != "disabled":  # pragma: no cover - wandb optional
            try:
                import wandb

                wandb.init(mode=wandb_mode, **(wandb_kwargs or {}))
                self._wandb = wandb
            except Exception:
                pass

    def write(self, stats: Dict[str, float], step: int) -> None:
        with self._lock:
            if self._tb is not None:
                for k, v in stats.items():
                    self._tb.add_scalar(k, v, step)
                self._tb.flush()
            if self._wandb is not None:  # pragma: no cover
                self._wandb.log(stats, step=step)

    def close(self) -> None:
        with self._lock:
            if self._tb is not None:
                self._tb.close()
                self._tb = None
