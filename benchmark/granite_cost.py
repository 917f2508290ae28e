"""Layer and parameter counts of a Granite 4.0-H model (``model_type``
granitemoehybrid, ``num_local_experts`` 0: whole blocks of a Mamba-2 mixer
or GQA attention, then a dense gated MLP) — kept with the benchmark so
that no later PR that claims a gain can move them (as ``peaks.py``,
``ssm_cost.py`` and ``sambay_cost.py`` keep theirs). The chunked scan's
operations and bytes are ``ssm_cost.ssd_scan_cost``, which takes the
geometry (chunk 256, 32 heads of 64 over ONE group of 128 states here).
From the HF config keys; no jax.
"""

from __future__ import annotations

from typing import Dict


def layer_types(cfg: Dict) -> list:
    """The ``num_hidden_layers`` entries of ``layer_types`` that are run,
    from ``first_layer_index`` on (0 where the key is absent)."""
    first = int(cfg.get("first_layer_index", 0))
    return cfg["layer_types"][first:first + cfg["num_hidden_layers"]]


def layer_counts(cfg: Dict) -> Dict[str, int]:
    """{``mamba`` | ``attention``: layers of it} of the configuration as it
    is run."""
    types = layer_types(cfg)
    return {name: types.count(name) for name in ("mamba", "attention")}


def mamba_runs(cfg: Dict) -> int:
    """Runs of consecutive ``mamba`` layers in the configuration as it is
    run: the program scans each run and so traces one scan a run."""
    types = layer_types(cfg)
    return sum(t == "mamba" and (i == 0 or types[i - 1] != "mamba")
               for i, t in enumerate(types))


def share_params(cfg: Dict) -> int:
    """Parameters one token multiplies through ON THIS SHARE in a forward
    pass — the N of 6·N·T for the cell's utilisation: a Mamba block's two
    projections, an attention block's four, the gated MLP's three in
    every block, and the sliced (tied) head. Norms, the convolution and
    the scan multiply elementwise or against activations and are not
    counted."""
    d, f, v = cfg["hidden_size"], cfg["shared_intermediate_size"], cfg[
        "vocab_size"]
    H, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    G, N = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    di = H * P
    mamba = d * (2 * di + 2 * G * N + H) + di * d
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg.get("head_dim") or d // nq
    attn = 2 * d * nq * dh + 2 * d * nkv * dh
    n = layer_counts(cfg)
    return int(n["mamba"] * mamba + n["attention"] * attn
               + cfg["num_hidden_layers"] * 3 * d * f + d * v)
