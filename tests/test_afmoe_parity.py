"""Trinity-Mini's family (``afmoe``) through the system against the
benchmark's plain reference (``benchmark/reference_afmoe.py``: float32, a
loop over layers, every held expert on every token) on seeded weights, on
the CPU at a tiny size that keeps every mechanism: hidden 64, 4 query / 2
key-value heads of 16, window 8 in rows of 40, 10 blocks = 2 dense
(sliding) + two periods of expert blocks cut as the published pattern
cuts them (S·dense, S·dense, S, F, S, S, S, F, S, S), 8 routed experts of
which a token takes 3 beside a shared one, gated attention, sandwich
norms, RoPE on the sliding blocks only, the embedding times sqrt(hidden).

Both sides compute in float32 here, so they differ by the order of float32
sums only: 2e-4 on logits of order 1 (tests/test_mellum_parity.py).
"""

import functools
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.algorithms import ppo_functional as F
from areal_tpu.base import telemetry
from areal_tpu.models import generate as gen
from areal_tpu.models import hf, moe as moemod, transformer
from areal_tpu.models.config import FULL, SLIDING, MoEConfig
from areal_tpu.parallel import sharding
from benchmark import reference_afmoe as ref

S, FA = "sliding_attention", "full_attention"
HF_KEYS = {
    "model_type": "afmoe", "num_hidden_layers": 10, "num_dense_layers": 2,
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "intermediate_size": 96, "moe_intermediate_size": 32,
    "vocab_size": 97, "num_experts": 8, "num_experts_per_tok": 3,
    "num_shared_experts": 1, "score_func": "sigmoid", "route_norm": True,
    "route_scale": 2.826, "n_group": 1, "topk_group": 1,
    "num_expert_groups": 1, "num_limited_groups": 1, "mup_enabled": True,
    "rms_norm_eps": 1e-5, "rope_theta": 10000, "rope_scaling": None,
    "sliding_window": 8, "global_attn_every_n_layers": 4,
    "layer_types": ([S, S, S, FA] * 3)[:10],
    "tie_word_embeddings": False, "max_position_embeddings": 131072,
    "load_balance_coeff": 0.001,
}
# one rank's share of the same model: experts 2 and 3 of the 8
SHARE_KEYS = {**HF_KEYS, "num_experts": 2, "num_routed_experts": 8,
              "expert_shard_count": 4, "expert_shard_index": 1}
KEYS = {"whole": HF_KEYS, "share": SHARE_KEYS}
# five blocks — a dense one, three sliding expert blocks and a full one,
# every kind of block — for what needs no more depth than that
CUT_LAYERS = {"num_hidden_layers": 5, "num_dense_layers": 1,
              "layer_types": [S, S, S, S, FA]}
CUT = {**HF_KEYS, **CUT_LAYERS}
CUT_KEYS = {which: {**keys, **CUT_LAYERS} for which, keys in KEYS.items()}
TOL = dict(atol=2e-4, rtol=2e-4)
NORMS = ("ln1", "ln2", "ln1_post", "ln2_post", "q_norm", "k_norm",
         "final_ln")
KINDS = ("sliding_dense",) * 2 + (SLIDING, FULL) + (
    SLIDING, SLIDING, SLIDING, FULL) + (SLIDING, SLIDING)


def model(keys, seed=0, scale=0.3):
    """(config, float32 params): init_params with the matrices scaled up
    (so that every mechanism matters; the embedding less, it is scaled by
    sqrt(hidden) again), the norm weights random around 1 and the router's
    choice bias random. Built once a set of keys: no test writes into the
    tree it gets."""
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
        elif leaf == "router_bias":
            flat[name] = 0.1 * jax.random.normal(k, x.shape)
        elif leaf == "embedding":
            flat[name] = x * (scale / 0.02 / 8)
        else:
            flat[name] = x * (scale / 0.02)
    return cfg, hf.unflatten_pytree(flat)


def tokens(seed=0, T=40):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(2, HF_KEYS["vocab_size"], T), jnp.int32)


def system_logits(params, cfg, tok, remat=False):
    T = tok.shape[0]
    out, _ = transformer.forward(
        params, cfg, tok[None], jnp.arange(T, dtype=jnp.int32)[None],
        segment_ids=jnp.ones((1, T), jnp.int32), attn_impl="reference",
        return_kv=False, remat=remat)
    return out[0]


def ppo_loss(logits, tok, seed=3):
    """The decoupled PPO actor loss on the logits of one sequence: seeded
    behaviour / proximal logprobs near the policy's and seeded advantages,
    the first quarter of the tokens a prompt."""
    T = tok.shape[0]
    ones = jnp.ones((1, T), jnp.int32)
    lp = F.token_logprobs_from_logits(logits[None], tok[None], ones)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    base = jax.lax.stop_gradient(lp)
    old = base + 0.1 * jax.random.normal(ks[0], lp.shape)
    prox = base + 0.05 * jax.random.normal(ks[1], lp.shape)
    adv = jax.random.normal(ks[2], lp.shape)
    mask = F.action_token_mask(
        ones, (jnp.arange(T) < T // 4).astype(jnp.int32)[None])
    loss, _ = F.actor_loss(lp, old, adv, mask, proximal_logprobs=prox)
    return loss


# ---- (a) the program against the reference ----

@pytest.mark.parametrize("which", sorted(KEYS))
def test_the_family_reads_the_pattern_the_prologue_the_share_and_the_ropes(
        which):
    cfg, params = model(KEYS[which])
    assert cfg.layer_kinds == KINDS and cfg.period_kinds == KINDS
    assert cfg.is_hybrid and not cfg.has_mixer_layers
    assert cfg.block_counts() == {
        "sliding/dense": 2, "sliding/experts": 6, "full/experts": 2}
    assert cfg.n_expert_layers == 8
    # RoPE on the sliding blocks, no position embedding on the full ones
    assert cfg.rope_of(FULL) is None and cfg.rope_of(SLIDING).base == 1e4
    assert cfg.rope_of("sliding_dense") == cfg.rope_of(SLIDING)
    assert cfg.window_of("sliding_dense") == 8 and cfg.window_of(FULL) is None
    ropes = transformer.rope_tables_by_kind(cfg, jnp.arange(5)[None])
    assert list(ropes) == [SLIDING, FULL] and ropes[FULL] == (None, None)
    assert (cfg.gated_attention, cfg.sandwich_norm, cfg.use_qk_norm,
            cfg.qk_norm_extent, cfg.scale_embeddings) == (
        True, True, True, "head", True)
    moe = cfg.moe
    assert (moe.router_score, moe.routed_scaling_factor, moe.norm_topk_prob,
            moe.capacity_factor, moe.shared_intermediate_dim,
            moe.aux_loss_coeff) == ("sigmoid", 2.826, True, None, 32, 0.0)
    assert (moe.num_experts, moe.first_expert, moe.n_routed) == (
        (8, 0, 8) if which == "whole" else (2, 2, 8))
    # parameters stacked per kind of block, and counted
    assert {k: v["ln1"].shape[0] for k, v in params["layers"].items()} == {
        "sliding_dense": 2, SLIDING: 6, FULL: 2}
    assert "w_gate" in params["layers"]["sliding_dense"]
    assert "router" not in params["layers"]["sliding_dense"]
    assert params["layers"][FULL]["wg"].shape == (2, 64, 64)
    assert transformer.param_count(cfg) == sum(
        x.size for x in jax.tree.leaves(params))
    # a token touches 3 of 8 experts: all of them held, or 3 x 2 / 8 of one
    one = 3 * 64 * 32
    assert transformer.param_count(cfg) - transformer.activated_param_count(
        cfg) == 8 * ((8 - 3) * one if which == "whole" else
                     2 * one - round(0.75 * one))
    # the spec tree has the parameters' structure
    specs = sharding.param_partition_specs(cfg)
    assert jax.tree.structure(jax.tree.map(lambda a: 0, params)) == \
        jax.tree.structure(jax.tree.map(
            lambda s: 0, specs,
            is_leaf=lambda x: isinstance(x, sharding.P)))
    # and back: the config.json the family writes reads to the same config
    again = hf.config_from_hf(types.SimpleNamespace(**hf.hf_config_dict(cfg)))
    assert again == cfg


@pytest.mark.parametrize("key,value", [
    ("n_group", 2), ("num_limited_groups", 2), ("score_func", "softmax"),
    ("rope_scaling", {"rope_type": "yarn"})])
def test_the_family_refuses_by_name_what_it_cannot_run(key, value):
    with pytest.raises(NotImplementedError, match=key if key != "score_func"
                       else "softmax"):
        hf.config_from_hf(types.SimpleNamespace(**{**HF_KEYS, key: value}))


def test_layer_types_come_from_the_global_period_where_the_key_is_absent():
    keys = {k: v for k, v in HF_KEYS.items() if k != "layer_types"}
    assert hf.config_from_hf(types.SimpleNamespace(**keys)).layer_kinds == KINDS


def test_mellum_refuses_a_dense_entry_of_mlp_layer_types_by_name():
    from test_mellum_parity import HF_KEYS as MELLUM

    sparse = hf.config_from_hf(types.SimpleNamespace(
        **MELLUM, mlp_layer_types=["sparse"] * 8))
    assert sparse == hf.config_from_hf(types.SimpleNamespace(**MELLUM))
    with pytest.raises(NotImplementedError, match="mlp_layer_types"):
        hf.config_from_hf(types.SimpleNamespace(
            **MELLUM, mlp_layer_types=["dense"] + ["sparse"] * 7))


@pytest.mark.parametrize("which", sorted(KEYS))
def test_logits_match_the_reference(which):
    cfg, params = model(KEYS[which])
    tok = tokens()
    np.testing.assert_allclose(system_logits(params, cfg, tok),
                               ref.logits(params, KEYS[which], tok), **TOL)


@pytest.mark.parametrize("which", sorted(KEYS))
def test_ppo_loss_and_gradients_match_the_reference(which):
    keys = CUT_KEYS[which]
    cfg, params = model(keys)
    tok = tokens(1)
    got_l, got_g = jax.jit(jax.value_and_grad(
        lambda p: ppo_loss(system_logits(p, cfg, tok, "full"), tok)))(params)
    want_l, want_g = jax.jit(jax.value_and_grad(
        lambda p: ppo_loss(ref.logits(p, keys, tok), tok)))(params)
    assert float(got_l) == pytest.approx(float(want_l), abs=1e-5)
    got_g, want_g = hf.flatten_pytree(got_g), hf.flatten_pytree(want_g)
    assert sorted(got_g) == sorted(want_g)
    for name in got_g:  # float32 sums in another order: 2e-4 of the largest
        scale = float(jnp.max(jnp.abs(want_g[name])))
        np.testing.assert_allclose(got_g[name], want_g[name],
                                   atol=2e-4 * scale, rtol=2e-3, err_msg=name)
    # the choice bias is the publisher's buffer: no gradient reaches it
    for kind in (SLIDING, FULL):
        assert not np.any(got_g[f"layers/{kind}/router_bias"])
        assert np.any(got_g[f"layers/{kind}/wg"])


# the wrong models of benchmark/check_limits_afmoe.py: (config keys
# changed, reference_afmoe ``wrong`` names)
WRONG = {
    "no_gate": ({}, {"no_gate"}),
    "rope_on_full": ({}, {"rope_on_full"}),
    "window_halved": ({"sliding_window": 4}, set()),
    "no_window": ({"sliding_window": 10 ** 6}, set()),
    "no_post_norms": ({}, {"no_post_norms"}),
    "gates_not_scaled": ({"route_scale": 1.0}, set()),
    "softmax_for_sigmoid": ({"score_func": "softmax"}, set()),
    "no_shared_expert": ({"num_shared_experts": 0}, set()),
    "dense_as_experts": ({}, {"dense_as_experts"}),
    "float8": ({}, {"float8"}),
}


@pytest.mark.parametrize("variant", sorted(WRONG))
def test_a_wrong_model_is_far_outside_the_tolerance(variant):
    """What the tolerance is FOR: each of the model's mechanisms, left out
    of the reference or got wrong in it, moves the logits by far more
    than it allows."""
    from benchmark import check_limits_afmoe

    assert set(check_limits_afmoe.wrong_models(HF_KEYS)) == set(WRONG) | {
        "as_published"}
    cfg, params = model(HF_KEYS)
    tok = tokens()
    got = system_logits(params, cfg, tok)
    keys, names = WRONG[variant]
    wrong = ref.logits(params, {**HF_KEYS, **keys}, tok, frozenset(names))
    assert float(jnp.max(jnp.abs(got - wrong))) > 50 * TOL["atol"]


# ---- (b) the shares of one expert block's FFN ----

def expert_layer(seed=0, D=32, F_=16, E=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    lp = {"router": jax.random.normal(ks[0], (D, E)) * 0.5,
          "router_bias": jax.random.normal(ks[1], (E,)) * 0.1,
          "e_gate": jax.random.normal(ks[2], (E, D, F_)) * 0.3,
          "e_up": jax.random.normal(ks[3], (E, D, F_)) * 0.3,
          "e_down": jax.random.normal(ks[4], (E, F_, D)) * 0.3,
          "s_gate": jax.random.normal(ks[5], (D, F_)) * 0.3,
          "s_up": jax.random.normal(ks[6], (D, F_)) * 0.3,
          "s_down": jax.random.normal(ks[7], (F_, D)) * 0.3}
    return lp, jax.random.normal(ks[8], (2, 12, D))


@pytest.mark.parametrize("shares", [2, 4, 16])
def test_the_shares_of_a_block_add_up_to_the_uncut_reference(shares):
    """Each share scores all 16 experts, normalises and scales the gates
    over all the chosen ones and adds only its held experts' part; the
    shared expert (like the attention around it) is whole on every rank
    and counted once: the parts sum to the uncut layer as the reference
    computes it, and the pairs that landed to the pairs routed."""
    lp, x = expert_layer()
    E, k = 16, 4
    held = E // shares
    cut = {"num_experts": E, "num_experts_per_tok": k, "route_norm": True,
           "route_scale": 2.826, "score_func": "sigmoid",
           "num_shared_experts": 1}
    xf = x.reshape(-1, x.shape[-1])
    want = ref.moe(xf, cut, lp)
    total, landed = 0.0, 0.0
    for i in range(shares):
        moe = MoEConfig(num_experts=held, top_k=k, capacity_factor=None,
                        norm_topk_prob=True, router_experts=E,
                        first_expert=i * held, router_score="sigmoid",
                        routed_scaling_factor=2.826,
                        shared_intermediate_dim=16 if i == 0 else None)
        mine = {"router": lp["router"], "router_bias": lp["router_bias"], **{
            n: lp[n][i * held:(i + 1) * held]
            for n in ("e_gate", "e_up", "e_down")}}
        if i == 0:  # the shared expert, once
            mine.update({n: lp[n] for n in ("s_gate", "s_up", "s_down")})
        y, aux = moemod.moe_mlp(x, mine, moe)
        assert float(aux["dropped_frac"]) == 0.0
        assert float(aux["routed_rows"]) == xf.shape[0] * k
        # the share against the reference given the same share
        part = ref.moe(xf, {**cut, "num_experts": held,
                            "expert_shard_index": i,
                            "num_shared_experts": int(i == 0)}, mine)
        np.testing.assert_allclose(y.reshape(part.shape), part, **TOL)
        total, landed = total + y, landed + float(aux["local_rows"])
    np.testing.assert_allclose(total.reshape(want.shape), want, **TOL)
    assert landed == xf.shape[0] * k


# ---- (c) the scan over the per-kind tree ----

PUBLISHED = {**HF_KEYS, "num_hidden_layers": 32, "hidden_size": 32,
             "num_attention_heads": 2, "num_key_value_heads": 1,
             "intermediate_size": 48, "moe_intermediate_size": 16,
             "layer_types": [S, S, S, FA] * 8}


@pytest.mark.parametrize("which", ["cut", "published"])
def test_scan_over_the_per_kind_tree_equals_a_loop_over_layers(which):
    """The 5-block cut, and the published 32-layer pattern at a small
    width: ((S·dense) x 2, S, F, (S, S, S, F) x 7) is no whole number of
    periods, so it is one period, run as ``period_runs`` cuts it."""
    keys = CUT if which == "cut" else PUBLISHED
    cfg, params = model(keys, scale=0.2)
    if which == "published":
        assert cfg.layer_kinds == ("sliding_dense",) * 2 + (SLIDING, FULL) + (
            SLIDING, SLIDING, SLIDING, FULL) * 7
        assert transformer.period_runs(cfg.period_kinds) == (
            (("sliding_dense",), 2), ((SLIDING, FULL, SLIDING, SLIDING), 7),
            ((SLIDING,), 1), ((FULL,), 1))
    tok = tokens(2)
    T = tok.shape[0]
    pos = jnp.arange(T, dtype=jnp.int32)[None]
    seg = jnp.ones((1, T), jnp.int32)
    h0 = params["embedding"][tok][None] * cfg.hidden_dim ** 0.5
    ropes = transformer.rope_tables_by_kind(cfg, pos)
    cos = {k: v[0] for k, v in ropes.items()}
    sin = {k: v[1] for k, v in ropes.items()}
    # one program a KIND of block, run a layer at a time
    block = jax.jit(transformer._block, static_argnums=(0, 10),
                    static_argnames="kind")
    h, loads, seen = h0, [], {}
    for kind in cfg.layer_kinds:
        j = seen.get(kind, 0)
        seen[kind] = j + 1
        lp = jax.tree.map(lambda a: a[j], params["layers"][kind])
        h, _, a = block(
            cfg, h, lp, cos, sin, seg, pos, None, None, None, "reference",
            kind=kind)
        if a is not None:
            loads.append(a["expert_load"])
    for remat in (False, "full"):
        got, aux = jax.jit(functools.partial(
            transformer.apply_layer_stack, cfg, attn_impl="reference",
            remat=remat))(h0, params["layers"], cos, sin, seg, pos)
        np.testing.assert_allclose(got, h, atol=2e-3, rtol=1e-4)
        # the expert blocks' outputs come back stacked in layer order
        np.testing.assert_allclose(aux["expert_load"], jnp.stack(loads),
                                   atol=1e-6)
    np.testing.assert_allclose(
        system_logits(params, cfg, tok), ref.logits(params, keys, tok),
        atol=1e-3, rtol=1e-3)


def test_the_new_scopes_are_on_the_blocks_ops_and_listed_apart():
    cfg, params = model(CUT)
    tok = tokens()
    text = jax.jit(lambda p: system_logits(p, cfg, tok)).lower(
        params).as_text(debug_info=True)
    for scope in telemetry.SANDWICH_SCOPES + ("shared_expert", "moe_router",
                                              "mlp", "moe"):
        assert f'"{scope}/' in text or f"/{scope}/" in text, scope
    assert not set(telemetry.SANDWICH_SCOPES) & set(
        telemetry.DEVICE_SCOPES + telemetry.MOE_SCOPES
        + telemetry.LATENT_MOE_SCOPES)


# ---- (d) decode through the cache ----

@pytest.mark.parametrize("which", sorted(KEYS))
def test_decode_through_the_cache_matches_the_packed_forward(which):
    """A prompt longer than the window, then greedy decode through the KV
    cache — one cache of ``[n_layers, ...]`` whatever a block's FFN, cut
    by kind for the scan: every step's logits against the packed forward
    over the sequence so far, and the last against the reference."""
    keys = CUT_KEYS[which]
    cfg, params = model(keys)
    P, N = 13, 4
    seq = [int(t) for t in np.asarray(tokens(5, P))]
    assert gen.decode_refusal(cfg) is None
    state = gen.prefill_state(params, cfg, jnp.asarray([seq], jnp.int32),
                              jnp.asarray([P], jnp.int32), P + N + 1,
                              attn_impl="reference")
    logits = state["last_logits"][0]
    kv = {"k": state["kv_k"], "v": state["kv_v"]}
    assert kv["k"].shape[0] == cfg.n_layers
    slots = jnp.arange(P + N + 1)
    packed = jax.jit(lambda p, tok: system_logits(p, cfg, tok))
    for step in range(N):
        want = packed(params, jnp.asarray(seq, jnp.int32))[-1]
        np.testing.assert_allclose(logits, want, **TOL)
        seq.append(int(jnp.argmax(want)))
        n = len(seq) - 1  # slot of the token being fed
        out, kv = transformer.forward(
            params, cfg, jnp.asarray([[seq[-1]]], jnp.int32),
            jnp.asarray([[n]], jnp.int32), kv_cache=kv,
            cache_write_index=jnp.asarray(n, jnp.int32),
            kv_valid=transformer.kv_valid_by_kind(
                cfg, (slots <= n)[None], (n - slots)[None]))
        logits = out[0, 0]
    np.testing.assert_allclose(
        logits, ref.logits(params, keys,
                           jnp.asarray(seq, jnp.int32))[-1], **TOL)


def test_generate_agrees_with_the_packed_forward_past_the_window():
    from areal_tpu.api.model import GenerationHyperparameters

    cfg, params = model(HF_KEYS)
    P, N = 12, 5
    prompt = tokens(7, P)
    g = GenerationHyperparameters(max_new_tokens=N, greedy=True)
    out = gen.generate_batch(  # no id is EOS: nothing stops or is masked
        params, cfg, prompt[None], jnp.asarray([P]), jax.random.PRNGKey(0),
        g, max_new_tokens=N, eos_token_id=10 ** 6, pad_token_id=0,
        attn_impl="reference")
    new = np.asarray(out["output_ids"])[0]
    lps = np.asarray(out["output_logprobs"])[0]
    seq = jnp.concatenate([prompt, jnp.asarray(new[:N], jnp.int32)])
    lp = jax.nn.log_softmax(system_logits(params, cfg, seq), -1)
    for j in range(N):
        assert int(jnp.argmax(lp[P + j - 1])) == int(new[j])
        assert float(lp[P + j - 1, new[j]]) == pytest.approx(
            float(lps[j]), abs=5e-4)


# ---- (e) weights in and out ----

@pytest.mark.parametrize("which", sorted(KEYS))
def test_hf_save_and_load_round_trip(which, tmp_path):
    cfg, params = model(KEYS[which])
    sd = hf.params_to_hf_state_dict(params, cfg)
    held = cfg.moe.num_experts
    for name in ("model.layers.0.mlp.gate_proj.weight",
                 "model.layers.0.self_attn.gate_proj.weight",
                 "model.layers.0.pre_mlp_layernorm.weight",
                 "model.layers.1.post_mlp_layernorm.weight",
                 "model.layers.2.mlp.router.gate.weight",
                 "model.layers.2.mlp.expert_bias",
                 f"model.layers.3.mlp.experts.{held - 1}.down_proj.weight",
                 "model.layers.9.mlp.shared_experts.up_proj.weight",
                 "model.layers.3.self_attn.q_norm.weight"):
        assert name in sd, name
    assert "model.layers.0.mlp.router.gate.weight" not in sd
    assert "model.layers.2.mlp.gate_proj.weight" not in sd
    assert f"model.layers.3.mlp.experts.{held}.down_proj.weight" not in sd
    assert sd["model.layers.0.self_attn.gate_proj.weight"].shape == (64, 64)
    assert sd["model.layers.2.mlp.router.gate.weight"].shape == (8, 64)
    hf.save_hf_checkpoint(params, cfg, str(tmp_path))
    cfg2, params2 = hf.load_hf_checkpoint(str(tmp_path))
    assert cfg2 == cfg
    got, want = hf.flatten_pytree(params2), hf.flatten_pytree(params)
    assert sorted(got) == sorted(want)
    for name in want:
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)


def test_param_count_at_the_cells_sizes():
    """The benchmark's configuration file, as the family reads it: one
    dense block + (S, S, S, F) expert blocks holding 8 of 128 experts and
    an eighth of the vocabulary — the issue's arithmetic, to the unit."""
    import json
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        "trinity-mini.json")
    with open(path) as f:
        keys = json.load(f)
    cfg = hf.config_from_hf(types.SimpleNamespace(**keys))
    assert cfg.layer_kinds == ("sliding_dense",) + (SLIDING,) * 3 + (FULL,)
    assert (cfg.moe.num_experts, cfg.moe.n_routed, cfg.moe.top_k) == (
        8, 128, 8)
    shapes = jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert n == transformer.param_count(cfg) == 504_409_856
    # a dense block, an expert block (8 held), embedding + head + norm
    assert n == 65_020_160 + 4 * 84_156_800 + 2 * 25_088 * 2048 + 2048
    # a token's 8 choices land on 8 x 8 / 128 = half an expert a block
    assert transformer.activated_param_count(cfg) == n - 4 * round(
        7.5 * 6_291_456)
