"""OLMoE through the system against the benchmark's plain reference
(``benchmark/reference_olmoe.py``: float32, every expert on every token) on
seeded weights, on the CPU at a tiny size: 2 layers, 8 experts of which a
token takes 2, the q/k norm over the whole projected vector, gates not
renormalised, no capacity.

Tolerances: both sides compute in float32 here, so they differ by the
order of float32 sums only (the system adds a token's chosen experts, the
reference all of them with zero gates): 2e-4 absolute on logits of order
1. A float32-vs-bfloat16 difference is ~1e-2, a lost (token, expert) pair
~1e-1 on these weights (``test_a_dropped_pair_is_far_outside_the_tolerance``
shows it), so either fails.
"""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.algorithms import ppo_functional as F
from areal_tpu.base import telemetry
from areal_tpu.models import hf, moe as moemod, transformer
from areal_tpu.parallel import mesh as pmesh
from areal_tpu.parallel import sharding as psh
from benchmark import reference_olmoe as ref

HF_KEYS = {
    "model_type": "olmoe", "num_hidden_layers": 2, "hidden_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "intermediate_size": 16, "vocab_size": 97, "num_experts": 8,
    "num_experts_per_tok": 2, "norm_topk_prob": False, "rope_theta": 10000,
    "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
    "max_position_embeddings": 256, "router_aux_loss_coef": 0.01,
}
TOL = dict(atol=2e-4, rtol=2e-4)


NORMS = ("ln1", "ln2", "q_norm", "k_norm", "final_ln")


def model(seed=0, scale=0.3):
    """(config, float32 params): init_params with the matrices scaled up
    (so that the experts matter) and the norm weights random around 1."""
    cfg = hf.config_from_hf(types.SimpleNamespace(**HF_KEYS))
    flat = hf.flatten_pytree(
        transformer.init_params(cfg, jax.random.PRNGKey(seed)))
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(flat))
    for (name, x), k in zip(sorted(flat.items()), keys):
        flat[name] = (1.0 + 0.1 * jax.random.normal(k, x.shape)
                      if name.split("/")[-1] in NORMS else x * (scale / 0.02))
    return cfg, hf.unflatten_pytree(flat)


def tokens(seed=0, B=4, T=24):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(2, HF_KEYS["vocab_size"], (B, T)),
                       jnp.int32)


def system_logits(params, cfg, tok):
    B, T = tok.shape
    pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    out, _, aux = transformer.forward(
        params, cfg, tok, pos, segment_ids=jnp.ones((B, T), jnp.int32),
        attn_impl="reference", return_aux=True)
    return out, aux


def reference_logits(params, tok):
    return jnp.stack([ref.logits(params, HF_KEYS, t) for t in tok])


def ppo_loss(logits, tok, seed=3):
    """The actor's clipped surrogate on the taken tokens' logprobs, with
    seeded advantages and behaviour logprobs."""
    rng = np.random.default_rng(seed)
    lp = jnp.take_along_axis(jax.nn.log_softmax(logits[:, :-1], -1),
                             tok[:, 1:, None], -1)[..., 0]
    old = lp + jnp.asarray(rng.normal(0, 0.1, lp.shape), jnp.float32)
    adv = jnp.asarray(rng.normal(0, 1, lp.shape), jnp.float32)
    loss, _ = F.actor_loss(lp, jax.lax.stop_gradient(old), adv,
                           jnp.ones(lp.shape, bool))
    return loss


def test_config_from_hf_on_the_catalog_keys():
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "configs", "olmoe-1b-7b.json")) as f:
        cfg = hf.config_from_hf(types.SimpleNamespace(**json.load(f)))
    assert (cfg.hidden_dim, cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim) == (
        2048, 16, 16, 128)
    assert (cfg.vocab_size, cfg.rotary_base, cfg.rms_norm_eps) == (
        50304, 10000, 1e-5)
    assert not cfg.tie_word_embeddings and not cfg.use_attention_bias
    assert cfg.use_qk_norm and cfg.qk_norm_extent == "proj"
    assert (cfg.q_norm_dim, cfg.k_norm_dim) == (2048, 2048)
    m = cfg.moe
    assert (m.num_experts, m.top_k, m.routed_intermediate_dim) == (64, 8, 1024)
    assert m.capacity_factor is None and not m.norm_topk_prob
    assert m.shared_intermediate_dim is None and m.aux_loss_coeff == 0.01
    # 6.9 B parameters at the published depth, 1.3 B of them activated
    full = cfg.__class__(**{**cfg.__dict__, "n_layers": 16})
    assert round(transformer.param_count(full) / 1e9, 1) == 6.9
    assert round(transformer.activated_param_count(full) / 1e9, 1) == 1.3
    # and back out as the family's config.json
    d = hf.hf_config_dict(cfg)
    assert d["model_type"] == "olmoe" and d["intermediate_size"] == 1024
    assert d["architectures"] == ["OlmoeForCausalLM"]
    assert "moe_intermediate_size" not in d and "head_dim" not in d
    assert hf.config_from_hf(types.SimpleNamespace(**d)) == cfg


def test_forward_logits_match_the_reference():
    cfg, params = model()
    tok = tokens()
    got, aux = system_logits(params, cfg, tok)
    np.testing.assert_allclose(got, reference_logits(params, tok), **TOL)
    assert float(aux["dropped_frac"]) == 0.0
    assert float(aux["routed_rows"]) == tok.size * 2


def test_ppo_loss_and_gradients_match_the_reference():
    cfg, params = model()
    tok = tokens(1)
    l_sys, g_sys = jax.jit(jax.value_and_grad(
        lambda p: ppo_loss(system_logits(p, cfg, tok)[0], tok)))(params)
    l_ref, g_ref = jax.jit(jax.value_and_grad(
        lambda p: ppo_loss(reference_logits(p, tok), tok)))(params)
    assert float(l_sys) == pytest.approx(float(l_ref), rel=1e-5, abs=1e-6)
    flat_s, flat_r = hf.flatten_pytree(g_sys), hf.flatten_pytree(g_ref)
    assert set(flat_s) == set(flat_r)
    for name in flat_r:
        scale = float(jnp.max(jnp.abs(flat_r[name]))) + 1e-12
        np.testing.assert_allclose(flat_s[name] / scale, flat_r[name] / scale,
                                   atol=5e-4, err_msg=name)
        assert float(jnp.max(jnp.abs(flat_r[name]))) > 0, name


def test_a_dropped_pair_is_far_outside_the_tolerance():
    """What the tolerance must catch: the same model with a capacity that
    loses some (token, expert) pairs."""
    cfg, params = model()
    tok = tokens()
    import dataclasses

    tight = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=0.5))
    got, aux = system_logits(params, tight, tok)
    assert float(aux["dropped_frac"]) > 0
    err = float(jnp.max(jnp.abs(got - reference_logits(params, tok))))
    assert err > 100 * TOL["atol"]


@pytest.mark.parametrize("skew", [False, True])
def test_e4_matches_one_device_and_the_reference(skew):
    """Experts over four (virtual) chips: logits, loss and gradients equal
    to one device's and the reference's — also when the router sends every
    token to the same two experts, which one shard owns."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    cfg, params = model()
    if skew:
        params = skewed(params)
    tok = tokens(2)
    mesh = pmesh.make_mesh(pmesh.ParallelSpec.parse("e4"))
    sharded = psh.shard_params(params, mesh, cfg)
    assert moemod.ep_eligible(mesh, cfg.moe, *tok.shape)

    def run(p):
        logits, aux = system_logits(p, cfg, tok)
        return ppo_loss(logits, tok), (logits, aux)

    with psh.activation_sharding(mesh):
        (l_ep, (lg_ep, aux)), g_ep = jax.jit(
            jax.value_and_grad(run, has_aux=True))(sharded)
    (l_1, (lg_1, aux_1)), g_1 = jax.jit(
        jax.value_and_grad(run, has_aux=True))(params)
    np.testing.assert_allclose(lg_ep, lg_1, **TOL)
    np.testing.assert_allclose(lg_ep, reference_logits(params, tok), **TOL)
    assert float(l_ep) == pytest.approx(float(l_1), rel=1e-5, abs=1e-6)
    assert float(aux["dropped_frac"]) == float(aux_1["dropped_frac"]) == 0.0
    ratio = float(aux["expert_load_ratio"])
    assert ratio == pytest.approx(float(aux_1["expert_load_ratio"]), rel=1e-5)
    assert (ratio == pytest.approx(4.0)) if skew else (ratio < 4.0)
    f_ep, f_1 = hf.flatten_pytree(g_ep), hf.flatten_pytree(g_1)
    for name in f_1:
        scale = float(jnp.max(jnp.abs(f_1[name]))) + 1e-12
        np.testing.assert_allclose(f_ep[name] / scale, f_1[name] / scale,
                                   atol=5e-4, err_msg=name)


def skewed(params):
    """The same weights with a zero router: every expert has the same
    probability, and both the system's top-k and the reference's sort take
    the lowest indices first, so every token goes to experts 0 and 1."""
    r = jnp.zeros_like(params["layers"]["router"])
    return {**params, "layers": {**params["layers"], "router": r}}


def test_skewed_and_balanced_routing_share_one_program():
    """Shapes are static under any routing: one compiled program serves a
    balanced router and one that sends every token to the same experts;
    nothing is dropped either way and both agree with the reference."""
    cfg, params = model()
    tok = tokens(4)
    fn = jax.jit(lambda p: system_logits(p, cfg, tok))
    for p in (params, skewed(params)):
        got, aux = fn(p)
        assert float(aux["dropped_frac"]) == 0.0
        np.testing.assert_allclose(got, reference_logits(p, tok), **TOL)
    assert fn._cache_size() == 1
    _, aux = fn(skewed(params))
    load = np.asarray(aux["expert_load"])
    assert load[:2].sum() == pytest.approx(1.0) and load[2:].sum() == 0.0


def test_prefill_and_decode_match_the_references_full_forward():
    """Serving: prefill a prompt, then decode through the KV cache feeding
    the reference's own greedy tokens; every step's logits against the
    reference's full forward over the sequence so far."""
    from areal_tpu.models import generate as gen

    cfg, params = model()
    P, N = 9, 6
    seq = [int(t) for t in np.asarray(tokens(5, 1, P)[0])]
    state = gen.prefill_state(params, cfg, jnp.asarray([seq], jnp.int32),
                              jnp.asarray([P], jnp.int32), P + N + 1,
                              attn_impl="reference")
    logits = state["last_logits"][0]
    kv = {"k": state["kv_k"], "v": state["kv_v"]}
    for step in range(N):
        want = ref.logits(params, HF_KEYS, jnp.asarray(seq, jnp.int32))[-1]
        np.testing.assert_allclose(logits, want, **TOL)
        seq.append(int(jnp.argmax(want)))
        n = len(seq) - 1  # slot of the token being fed
        out, kv = transformer.forward(
            params, cfg, jnp.asarray([[seq[-1]]], jnp.int32),
            jnp.asarray([[n]], jnp.int32), kv_cache=kv,
            cache_write_index=jnp.asarray(n, jnp.int32),
            kv_valid=(jnp.arange(P + N + 1) <= n)[None])
        logits = out[0, 0]


def test_moe_scopes_are_on_the_compiled_ops():
    cfg, params = model()
    tok = tokens()
    text = jax.jit(lambda p: system_logits(p, cfg, tok)[0]).lower(
        params).as_text(debug_info=True)
    assert set(telemetry.MOE_SCOPES) == {
        "moe_router", "moe_dispatch", "moe_exchange", "moe_experts"}
    for scope in ("moe_router", "moe_dispatch", "moe_experts"):
        assert f"moe/{scope}" in text, scope


def test_trainer_path_e4_against_one_device(tmp_path):
    """``build_trainer_config`` → backend → ``PPOActorInterface`` with
    ``allocation_mode=e4`` for a model given as a checkpoint directory's
    ``config.json``: inference and one train step on four (virtual) chips
    against the same on one."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    import areal_tpu.algorithms  # noqa: F401
    import areal_tpu.backend.jax_train  # noqa: F401
    from areal_tpu.api import cli_args as CA
    from areal_tpu.api.data import SequenceSample
    from areal_tpu.api.model import Model, make_backend, make_interface
    from areal_tpu.experiments import make_experiment_cls

    ckpt = tmp_path / "ckpt"
    ckpt.mkdir()
    (ckpt / "config.json").write_text(json.dumps(HF_KEYS))
    rng = np.random.default_rng(0)
    lens = [40, 33, 25, 40, 17, 29, 36, 22]
    n = sum(lens)
    pm = np.concatenate([np.r_[np.ones(5), np.zeros(x - 5)] for x in lens])
    data = {
        "packed_input_ids": rng.integers(2, 97, n).astype(np.int32),
        "prompt_mask": pm.astype(np.int32),
        "packed_logprobs": np.zeros(n, np.float32),
        "rewards": rng.integers(0, 2, len(lens)).astype(np.float32) * 2 - 1,
        "seq_no_eos_mask": np.ones(len(lens), np.float32),
        "version_start": np.zeros(len(lens), np.int32),
        "version_end": np.zeros(len(lens), np.int32),
    }

    def run(alloc):
        cfg, params = model()  # the engine's optimizer step donates them
        exp = CA.apply_overrides(make_experiment_cls("async-ppo-math")(), [
            "experiment_name=t", f"trial_name={alloc}",
            f"cluster.fileroot={tmp_path}/exps", "mock_tokenizer=true",
            "n_gpus_per_node=4", f"actor.path={ckpt}",
            f"allocation_mode={alloc}", "group_size=4",
            "dataset.train_bs_n_seqs=2", "ppo.disable_value=true",
            "ppo.kl_ctl=0", "ppo.use_decoupled_loss=true",
            "ppo.ppo_n_minibatches=1", "actor.bf16=false",
            "actor_train.mb_spec.max_tokens_per_mb=512",
            "actor_inf.mb_spec.max_tokens_per_mb=512"])
        CA.validate_config(exp)
        tcfg = exp.build_trainer_config(async_mode=True)
        rc = tcfg.models["actor"]
        backend = make_backend(rc.backend, **{
            "train": rc.train, **rc.backend_args, "attn_impl": "reference"})
        m = backend.initialize(Model("actor", (cfg, params)), tcfg.ft_spec)
        ifs = {k: make_interface(tcfg.mfcs[k].interface,
                                 **tcfg.mfcs[k].interface_args)
               for k in ("actor_inf", "actor_train")}
        s = SequenceSample.from_default(
            ids=[f"s{i}" for i in range(len(lens))],
            data={k: v.copy() for k, v in data.items()}, seqlens=lens,
            metadata={"group": [f"g{i // 4}" for i in range(len(lens))]})
        prox = ifs["actor_inf"].inference(m, s, exp.actor_inf.mb_spec)
        s.data["packed_logprobs"] = (
            prox.data["prox_logprobs"] * (1 - pm)).astype(np.float32)
        s.update_(prox)
        stats = ifs["actor_train"].train_step(m, s, exp.actor_train.mb_spec)
        return m.module, np.asarray(prox.data["prox_logprobs"]), stats

    eng4, lp4, st4 = run("e4")
    eng1, lp1, st1 = run("d1")
    assert eng4.mesh is not None and eng4.rows_multiple == 4
    assert dict(eng4.mesh.shape)["ep"] == 4 and eng1.mesh is None
    np.testing.assert_allclose(lp4, lp1, atol=1e-4)
    assert st4["moe_dropped_frac"] == st1["moe_dropped_frac"] == 0.0
    # every real token routes top_k pairs, per layer, over the step
    assert st4["moe_routed_rows"] == st1["moe_routed_rows"] == 2 * n
    for k in ("actor_loss", "grad_norm", "importance_weight",
              "moe_expert_load_ratio"):
        assert st4[k] == pytest.approx(st1[k], rel=2e-3, abs=1e-6), k
