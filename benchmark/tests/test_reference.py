"""``reference.py`` (plain float32, independent of the program's model
code) against ``areal_tpu.models.transformer.forward`` in float32 at a
tiny size, for both configurations' shapes: qkv bias, tied and untied
head, grouped-query 7x and 6x."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness, reference, weights

CONFIGS = [c["name"] for c in harness.load_benchmark()["configs"]]


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_agrees_with_the_program_in_float32(name):
    from areal_tpu.models import transformer

    with open(os.path.join(harness.BENCH_DIR, "configs", name + ".json")) as f:
        full = json.load(f)
    tiny = dict(full, num_hidden_layers=2, intermediate_size=64,
                hidden_size=full["num_attention_heads"] * 8, vocab_size=300)
    cfg = weights.model_config(tiny)
    assert cfg.n_q_heads // cfg.n_kv_heads == (
        full["num_attention_heads"] // full["num_key_value_heads"])
    assert cfg.use_attention_bias
    assert cfg.tie_word_embeddings == full["tie_word_embeddings"]
    params = weights.make_params(cfg, seed=3)
    assert float(jnp.abs(params["layers"]["bq"]).max()) > 0  # bias counts
    toks = np.random.default_rng(0).integers(2, 300, 41).astype(np.int32)
    got, _ = transformer.forward(
        params, cfg, jnp.asarray(toks[None]), jnp.arange(41)[None],
        segment_ids=jnp.ones((1, 41), jnp.int32), attn_impl="reference")
    ref = reference.logits(params, tiny, jnp.asarray(toks))
    # float32 on both sides, different order of operations only
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref),
                               atol=2e-5, rtol=0)
    lp = reference.token_logprobs(params, tiny, toks)
    assert lp.shape == (40,) and bool((lp <= 0).all())


def test_weights_differ_by_seed_and_version_and_keep_the_dtype():
    with open(os.path.join(harness.BENCH_DIR, "configs",
                           CONFIGS[0] + ".json")) as f:
        tiny = dict(json.load(f), num_hidden_layers=1, intermediate_size=32,
                    hidden_size=112, vocab_size=64)
    cfg = weights.model_config(tiny)
    a = weights.make_params(cfg, 1)["layers"]["wq"]
    assert a.dtype == jnp.float32
    assert bool((a == weights.make_params(cfg, 1)["layers"]["wq"]).all())
    assert not bool((a == weights.make_params(cfg, 2)["layers"]["wq"]).all())
    assert not bool(
        (a == weights.make_params(cfg, 1, version=1)["layers"]["wq"]).all())
    assert weights.make_params(cfg, 1, dtype="bfloat16")[
        "embedding"].dtype == jnp.bfloat16
