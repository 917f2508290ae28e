"""Configuration file → the program's model config, and weights from
``--seed`` made on the device in one jitted call, in the dtype they are
served or trained in. Imports jax and the program: only device-owning
driver processes (and CPU tests) import this module.
"""

from __future__ import annotations

import types
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp


def model_config(cfg_file: Dict[str, Any]):
    """The TransformerConfig the program's own HF family mapping builds
    from the public ``config.json`` keys the configuration file holds —
    the path a checkpoint's config takes (``models/hf.config_from_hf``)."""
    from areal_tpu.models import hf

    return hf.config_from_hf(types.SimpleNamespace(**cfg_file))


def make_params(model_cfg, seed: int, version: int = 0, dtype="float32"):
    """Random weights of ``(seed, version)``, on the default device, in one
    jitted call. The layout is the program's (``transformer.init_params``);
    its zero q/k/v biases are replaced by random ones so that the bias path
    counts in the comparison with the reference."""
    from areal_tpu.models import transformer

    import dataclasses

    cfg = dataclasses.replace(model_cfg, dtype=str(jnp.dtype(dtype)))

    @jax.jit
    def build(key):
        k_init, k_bias = jax.random.split(key)
        p = transformer.init_params(cfg, k_init)
        layers = dict(p["layers"])
        for i, name in enumerate(("bq", "bk", "bv")):
            if name in layers:
                b = layers[name]
                layers[name] = (0.02 * jax.random.normal(
                    jax.random.fold_in(k_bias, i), b.shape)).astype(b.dtype)
        return {**p, "layers": layers}

    key = jax.random.fold_in(jax.random.PRNGKey(int(seed)), int(version))
    return jax.block_until_ready(build(key))


def flat_stats(params) -> Dict[str, Tuple[Tuple[int, ...], float, float]]:
    """{flat name: (shape, mean, std)} — what a CPU-side publisher needs to
    draw another version of the same distribution without the layout."""
    from areal_tpu.models.hf import flatten_pytree

    out = {}
    for name, a in flatten_pytree(params).items():
        a32 = a.astype(jnp.float32)
        out[name] = (tuple(int(s) for s in a.shape), float(a32.mean()),
                     float(a32.std()))
    return out
