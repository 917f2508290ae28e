"""Nemotron 3 (``model_type`` nemotron_h) through the system against the
benchmark's plain reference (``benchmark/reference_nemotron_h.py``:
float32, the recurrence a token at a time, every held expert on every
token, one document at a time) on seeded weights, on the CPU at a tiny
size: hidden 64, Mamba-2 with 8 heads of 8 in 2 groups and a state of 16
in chunks of 8, 4 query / 2 key-value heads of 16 with no position
embedding, 8 latent experts (latent 32, width 48, not gated, relu²) of
which a token takes 3 behind a sigmoid router with a choice bias, a shared
expert of 96, 22 layers = two periods of ``EMEMEMEMEM*``.

Both sides compute in float32 here, so they differ by the order of
float32 sums only (tests/test_mellum_parity.py: 2e-4 on logits of order
1). A reset left off, the norm taken over every group at once, gates that
are not scaled, a choice without its bias or ``silu`` in place of
``relu²`` move logits by 1e-2 and more on these weights.
"""

import functools
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import hf, transformer
from areal_tpu.models.config import ATTENTION_ONLY, MAMBA, MOE_ONLY, SSMConfig
from benchmark import reference_nemotron_h as ref

PERIOD = "EMEMEMEMEM*"
HF_KEYS = {
    "model_type": "nemotron_h", "num_hidden_layers": 22,
    "hybrid_override_pattern": PERIOD * 2, "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 48, "vocab_size": 97, "mamba_num_heads": 8,
    "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 16,
    "conv_kernel": 4, "chunk_size": 8, "n_routed_experts": 8,
    "num_experts_per_tok": 3, "moe_intermediate_size": 48,
    "moe_latent_size": 32, "moe_shared_expert_intermediate_size": 96,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 5.0, "n_group": 1, "topk_group": 1,
    "mlp_hidden_act": "relu2", "norm_eps": 1e-5,
    "tie_word_embeddings": False, "max_position_embeddings": 4096,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
}
# one rank's share of the same model: experts 2 and 3 of the 8
SHARE_KEYS = {**HF_KEYS, "n_routed_experts": 2, "num_routed_experts": 8,
              "expert_shard_count": 4, "expert_shard_index": 1}
KEYS = {"whole": HF_KEYS, "share": SHARE_KEYS}
TOL = dict(atol=2e-4, rtol=2e-4)
NORMS = ("ln", "norm", "final_ln")
AS_DRAWN = ("conv_w", "dt_bias", "A_log")  # the published ranges
JITTERED = ("conv_b", "D", "router_bias")


def model(keys, seed=0, scale=0.3):
    """(config, float32 params): init_params with the matrices scaled up
    (so that every mixer matters), the norm weights random around 1, the
    convolution's bias, the skip ``D`` and the router's choice bias
    random, and the decay's parameters as the program draws them. Built
    once a set of keys: no test writes into the tree it gets."""
    return _model(json.dumps(keys, sort_keys=True), seed, scale)


@functools.lru_cache(maxsize=None)
def _model(keys, seed, scale):
    cfg = hf.config_from_hf(types.SimpleNamespace(**json.loads(keys)))
    flat = hf.flatten_pytree(
        transformer.init_params(cfg, jax.random.PRNGKey(seed)))
    rngs = jax.random.split(jax.random.PRNGKey(seed + 1), len(flat))
    for (name, x), k in zip(sorted(flat.items()), rngs):
        leaf = name.split("/")[-1]
        if leaf in NORMS:
            flat[name] = 1.0 + 0.1 * jax.random.normal(k, x.shape)
        elif leaf in JITTERED:
            flat[name] = x + 0.1 * jax.random.normal(k, x.shape)
        elif leaf not in AS_DRAWN:
            flat[name] = x * (scale / 0.02)
    return cfg, hf.unflatten_pytree(flat)


def tokens(seed=0, T=43):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(2, HF_KEYS["vocab_size"], T), jnp.int32)


def system_logits(params, cfg, tok, remat=False):
    T = tok.shape[0]
    out, _ = transformer.forward(
        params, cfg, tok[None], jnp.arange(T, dtype=jnp.int32)[None],
        segment_ids=jnp.ones((1, T), jnp.int32), attn_impl="reference",
        return_kv=False, remat=remat)
    return out[0]


def mean_logprob(logits, tok):
    lp = jax.nn.log_softmax(logits[:-1], -1)
    return jnp.mean(jnp.take_along_axis(lp, tok[1:, None], -1))


# ---- (a) the program against the reference ----

@pytest.mark.parametrize("which", sorted(KEYS))
def test_the_family_reads_the_pattern_the_mixers_and_the_share(which):
    cfg, params = model(KEYS[which])
    period = tuple({"E": MOE_ONLY, "M": MAMBA, "*": ATTENTION_ONLY}[c]
                   for c in PERIOD)
    assert cfg.layer_kinds == period * 2 and cfg.period_kinds == period
    assert cfg.is_hybrid and cfg.pos_embedding == "none"
    assert cfg.ssm == SSMConfig(n_heads=8, head_dim=8, n_groups=2,
                                state_dim=16, conv_kernel=4, chunk_size=8)
    moe = cfg.moe
    assert (moe.router_score, moe.routed_scaling_factor, moe.latent_dim,
            moe.gated_experts, moe.expert_act, moe.capacity_factor) == (
        "sigmoid", 5.0, 32, False, "relu2", None)
    assert (moe.num_experts, moe.first_expert, moe.n_routed) == (
        (8, 0, 8) if which == "whole" else (2, 2, 8))
    # parameters stacked per kind, and counted
    assert {k: v["ln"].shape[0] for k, v in params["layers"].items()} == {
        MAMBA: 10, MOE_ONLY: 10, ATTENTION_ONLY: 2}
    assert transformer.param_count(cfg) == sum(
        x.size for x in jax.tree.leaves(params))
    # a token touches 3 of 8 experts: all of them held, or 3 x 2 / 8 of one
    one = 2 * 32 * 48
    assert transformer.param_count(cfg) - transformer.activated_param_count(
        cfg) == 10 * ((8 - 3) * one if which == "whole" else
                      2 * one - round(0.75 * one))
    # and back: the config.json the family writes reads to the same config
    again = hf.config_from_hf(types.SimpleNamespace(**hf.hf_config_dict(cfg)))
    assert again == cfg


@pytest.mark.parametrize("which", sorted(KEYS))
def test_logits_match_the_reference(which):
    cfg, params = model(KEYS[which])
    tok = tokens()  # 43 tokens: no multiple of the chunk
    np.testing.assert_allclose(system_logits(params, cfg, tok),
                               ref.logits(params, KEYS[which], tok), **TOL)


@pytest.mark.parametrize("which", sorted(KEYS))
def test_loss_and_gradients_match_the_reference(which):
    cfg, params = model(KEYS[which])
    tok = tokens(1)
    got_l, got_g = jax.jit(jax.value_and_grad(
        lambda p: mean_logprob(system_logits(p, cfg, tok, "full"), tok)))(
            params)
    want_l, want_g = jax.jit(jax.value_and_grad(
        lambda p: mean_logprob(ref.logits(p, KEYS[which], tok), tok)))(params)
    assert float(got_l) == pytest.approx(float(want_l), abs=1e-5)
    got_g, want_g = hf.flatten_pytree(got_g), hf.flatten_pytree(want_g)
    assert sorted(got_g) == sorted(want_g)
    for name in got_g:  # float32 sums in another order: 2e-4 of the largest
        scale = float(jnp.max(jnp.abs(want_g[name])))
        np.testing.assert_allclose(got_g[name], want_g[name],
                                   atol=2e-4 * scale, rtol=2e-3, err_msg=name)
    # the choice bias is the publisher's buffer: no gradient reaches it
    assert not np.any(got_g["layers/moe_only/router_bias"])


WRONG = {
    "gates_not_scaled": {"routed_scaling_factor": 1.0},
    "silu_for_relu2": {"mlp_hidden_act": "silu"},
    "no_group_in_the_norm": "gated_norm",
    "no_choice_bias": "router_bias",
    "state_in_bfloat16": "scan",
}


@pytest.mark.parametrize("variant", sorted(WRONG))
def test_a_wrong_model_is_far_outside_the_tolerance(variant, monkeypatch):
    cfg, params = model(HF_KEYS)
    tok = tokens(2)
    keys, what = HF_KEYS, WRONG[variant]
    if isinstance(what, dict):
        keys = {**HF_KEYS, **what}
    elif what == "gated_norm":  # one RMS over all of d_inner
        real = ref.gated_norm
        monkeypatch.setattr(ref, "gated_norm", lambda y, z, w, groups, eps:
                            real(y, z, w, 1, eps))
    elif what == "scan":
        real_scan = ref.scan
        monkeypatch.setattr(ref, "scan", lambda *a: real_scan(
            *a, state_dtype=jnp.bfloat16))
    got = system_logits(params, cfg, tok)
    wrong = ref.logits(
        params if what != "router_bias" else jax.tree_util.tree_map_with_path(
            lambda p, x: x * 0 if "router_bias" in jax.tree_util.keystr(p)
            else x, params), keys, tok)
    # bfloat16 states move these tiny logits least: 9e-4, four times TOL
    floor = 5e-4 if variant == "state_in_bfloat16" else 1e-2
    assert float(jnp.max(jnp.abs(got - wrong))) > floor


# ---- (b) packed rows: the scan and the convolution reset ----

@pytest.mark.parametrize("place", [1, 2], ids=["second", "third"])
@pytest.mark.parametrize("remat", [False, "full"], ids=["keep", "rerun"])
def test_a_document_packed_later_in_a_row_gives_what_it_gives_alone(
        place, remat):
    """Logits AND every gradient of a document that sits behind others
    in its packed row are those of the document alone: the state and the
    convolution's taps stop at its first token, forward and backward."""
    keys = {**HF_KEYS, "num_hidden_layers": 11}  # one period
    cfg, params = model(keys)
    lens = [13, 21, 17]  # starts at 13 and 34: inside a chunk of 8
    docs = [tokens(10 + i, n) for i, n in enumerate(lens)]
    L = 64
    row = jnp.concatenate(docs + [jnp.zeros(L - sum(lens), jnp.int32)])
    seg = jnp.concatenate([jnp.full(n, i + 1, jnp.int32)
                           for i, n in enumerate(lens)]
                          + [jnp.zeros(L - sum(lens), jnp.int32)])
    pos = jnp.concatenate([jnp.arange(n, dtype=jnp.int32) for n in lens]
                          + [jnp.zeros(L - sum(lens), jnp.int32)])
    start, n = sum(lens[:place]), lens[place]

    def packed(p):
        out, _ = transformer.forward(
            p, cfg, row[None], pos[None], segment_ids=seg[None],
            attn_impl="reference", return_kv=False, remat=remat)
        return out[0, start:start + n]

    def alone(p):
        return system_logits(p, cfg, docs[place], remat)

    def logits_and_grads(logits_of):
        """ONE program: the document's logits and every gradient of its
        mean logprob."""
        def loss(p):
            logits = logits_of(p)
            return mean_logprob(logits, docs[place]), logits

        (_, logits), grads = jax.jit(
            jax.value_and_grad(loss, has_aux=True))(params)
        return logits, hf.flatten_pytree(grads)

    l_packed, g_packed = logits_and_grads(packed)
    l_alone, g_alone = logits_and_grads(alone)
    np.testing.assert_allclose(l_packed, l_alone, **TOL)
    np.testing.assert_allclose(
        l_packed, ref.logits(params, keys, docs[place]), **TOL)
    for name in g_alone:
        scale = float(jnp.max(jnp.abs(g_alone[name]))) or 1.0
        np.testing.assert_allclose(g_packed[name], g_alone[name],
                                   atol=2e-4 * scale, rtol=2e-3, err_msg=name)


@pytest.mark.parametrize("batch", [0, 1, 2])
def test_the_benchmarks_placed_later_trajectory_sits_behind_its_own_row(
        batch, monkeypatch):
    """``drivers/train_hybrid.placed_later`` on the cell's own lengths and
    micro-batch size, the engine stubbed by the packer alone: the
    trajectories it names as ahead of the chosen one lie in the SAME
    micro-batch and row and fill it as far as the chosen one's column —
    what the check of the reset, and its control in
    ``check_limits_nemotron_h.py`` (the row with the reset left off), take
    for the packed row."""
    import json
    import os

    from areal_tpu.api.data import MicroBatchSpec
    from areal_tpu.backend import microbatch as mbu
    from benchmark import traffic
    from benchmark.drivers import train_hybrid as drv
    from benchmark.drivers.train import to_sample

    with open(os.path.join(os.path.dirname(drv.__file__), os.pardir,
                           "traffic", "train-agent-4k.json")) as f:
        shape = json.load(f)["shape"]
    raw = traffic.make_train_batches(shape, 3, 4, 4, 7, 16384)[batch]
    raw["packed_logprobs"] = np.zeros(len(raw["packed_input_ids"]),
                                      np.float32)
    sample = to_sample(raw, "b")
    lens = [int(n) for n in sample.total_lens("packed_input_ids")]
    spec = MicroBatchSpec(max_tokens_per_mb=4096)

    monkeypatch.setattr(mbu, "split_into_microbatches",
                        mbu.split_into_microbatches)  # restored afterwards
    engine = types.SimpleNamespace(
        forward=lambda: mbu.split_into_microbatches(sample, spec))
    placements = drv.Placements(engine)
    inference = types.SimpleNamespace(inference=lambda *_: (
        engine.forward(), types.SimpleNamespace(
            data={"prox_logprobs": np.zeros(sum(lens), np.float32)}))[1])
    got, toks, where = drv.placed_later(
        {"actor_inf": inference}, None, spec, sample, placements)

    assert len({mb for mb, _, _ in placements.at.values()}) > 1
    i = where["trajectory"]
    assert where["column"] > 0 and where["ahead_in_row"]
    assert sum(lens[j] for j in where["ahead_in_row"]) == where["column"]
    for j in where["ahead_in_row"]:
        assert placements.at[j][:2] == (where["micro_batch"], where["row"])
    assert where["tokens"] == min(lens[i], drv.REFERENCE_TOKENS)
    start = sum(lens[:i])
    np.testing.assert_array_equal(
        toks, raw["packed_input_ids"][start:start + where["tokens"]])
    assert len(got) == where["tokens"] - 1
