"""Kimi-Linear (``model_type`` kimi_linear) through the system against the
benchmark's plain reference (``benchmark/reference_kimi_linear.py``:
float32, the Kimi Delta Attention rule a TOKEN at a time, latent attention
as a masked softmax of one document with nothing rotated, every held
expert on every token, one document at a time) on seeded weights, on the
CPU at a tiny size: hidden 32, 4 KDA heads of 8 through gates of rank 8, 4
attention heads of 12 + 4 over a value of 8 without a query latent, a dense
FFN of 48 on block 1, 8 experts of 24 (3 a token, gates x 2.446) beside a
shared expert after it, the cut's pattern ``D K K A`` (blocks 1, 6, 7, 8).

Both sides compute in float32 here, so they differ by the order of float32
sums only; every fault ``reference.WRONG`` names moves logprobs by far more.
"""

import dataclasses
import functools
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import hf, kda, mla, moe, transformer
from areal_tpu.models.config import FULL, KDA
from benchmark import reference_kimi_linear as ref

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HF_KEYS = {
    "model_type": "kimi_linear", "num_hidden_layers": 4,
    "held_layers": [1, 6, 7, 8], "first_k_dense_replace": 1,
    "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 4,
    "head_dim": 8, "q_lora_rank": None, "kv_lora_rank": 8,
    "qk_nope_head_dim": 12, "qk_rope_head_dim": 4, "v_head_dim": 8,
    "mla_use_nope": True,
    "linear_attn_config": {
        "kda_layers": [1, 2, 3, 5, 6, 7], "full_attn_layers": [4, 8],
        "num_heads": 4, "head_dim": 8, "short_conv_kernel_size": 4},
    "intermediate_size": 48, "moe_intermediate_size": 24, "vocab_size": 67,
    "rms_norm_eps": 1e-5, "rope_theta": 10000, "rope_scaling": None,
    "num_experts": 8, "num_shared_experts": 1, "num_experts_per_token": 3,
    "moe_router_activation_func": "sigmoid", "moe_renormalize": True,
    "moe_layer_freq": 1, "num_expert_group": 1, "topk_group": 1,
    "use_grouped_topk": True, "routed_scaling_factor": 2.446,
    "num_nextn_predict_layers": 0, "tie_word_embeddings": False,
    "hidden_act": "silu", "model_max_length": 4096,
}
TOL = dict(atol=3e-4, rtol=3e-4)
NORMS = ("ln1", "ln2", "final_ln", "kv_a_norm", "kda_norm")
DENSE = "kda_dense"
T = 29


class _frozen(dict):
    """The HF keys as a static (hashable) argument of ``jax.jit``."""

    def __hash__(self):
        return hash(json.dumps(self, sort_keys=True))


@functools.lru_cache(maxsize=None)
def model(seed=0, scale=0.3):
    """(config, float32 params): init_params with the matrices scaled up
    (so that every gate, latent and expert matters), the norm weights
    random around 1 and the choice bias drawn wide enough to change
    choices; ``A_log`` and ``dt_bias`` as drawn."""
    cfg = hf.config_from_hf(types.SimpleNamespace(**HF_KEYS))

    @jax.jit
    def build():
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
                flat[name] = x * 40.0
            elif leaf not in ("kda_A_log", "kda_dt_bias", "kda_conv"):
                flat[name] = x * (scale / 0.02)
        return hf.unflatten_pytree(flat)

    return cfg, build()


def tokens(seed=0, n=T):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(2, HF_KEYS["vocab_size"], n), jnp.int32)


def packed_row(lens, width, seed=10):
    docs = [tokens(seed + i, n) for i, n in enumerate(lens)]
    pad = width - sum(lens)
    row = jnp.concatenate(docs + [jnp.zeros(pad, jnp.int32)])[None]
    seg = jnp.asarray([sum(([i + 1] * n for i, n in enumerate(lens)), [])
                       + [0] * pad], jnp.int32)
    pos = jnp.asarray([sum((list(range(n)) for n in lens), []) + [0] * pad],
                      jnp.int32)
    return row, seg, pos, docs


@functools.partial(jax.jit, static_argnames=("cfg", "remat"))
def system_logits(params, cfg, tok, seg=None, pos=None, remat=False):
    one = tok.ndim == 1
    if one:
        tok = tok[None]
    B, n = tok.shape
    seg = jnp.ones((B, n), jnp.int32) if seg is None else seg
    pos = jnp.broadcast_to(jnp.arange(n), (B, n)) if pos is None else pos
    out, _ = transformer.forward(
        params, cfg, tok, pos, segment_ids=seg, attn_impl="reference",
        return_kv=False, remat=remat)
    return out[0] if one else out


def logprobs_of(lg, tok):
    lp = jax.nn.log_softmax(lg[:-1], -1)
    return jnp.take_along_axis(lp, tok[1:, None], -1)[:, 0]


# ---- (a) the family ----

def test_the_family_reads_the_blocks():
    cfg, params = model()
    assert cfg.layer_kinds == (DENSE, KDA, KDA, FULL)
    assert cfg.held_layers == (1, 6, 7, 8)
    assert cfg.pos_embedding == "none" and cfg.rotary_dim == 0
    assert cfg.mla.q_lora_rank is None and cfg.o_dim == 4 * 8
    assert (cfg.kda.n_heads, cfg.kda.head_dim, cfg.kda.gate_rank) == (4, 8, 8)
    assert cfg.moe.router_score == "sigmoid"
    assert cfg.moe.routed_scaling_factor == 2.446
    assert set(params["layers"]) == {DENSE, KDA, FULL}
    assert params["layers"][KDA]["kda_qkv"].shape == (2, 32, 96)
    assert params["layers"][FULL]["wq"].shape == (1, 32, 64)
    assert params["layers"][FULL]["wo"].shape == (1, 32, 32)
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    assert n == transformer.param_count(cfg)
    # the first layers of a cut of the cut (a rehearsal's two blocks)
    two = hf.config_from_hf(types.SimpleNamespace(
        **{**HF_KEYS, "num_hidden_layers": 2}))
    assert two.layer_kinds == (DENSE, KDA) and two.held_layers == (1, 6)
    # without the key: blocks 1 .. n
    plain = hf.config_from_hf(types.SimpleNamespace(
        **{k: v for k, v in HF_KEYS.items() if k != "held_layers"}))
    assert plain.layer_kinds == (DENSE, KDA, KDA, FULL)
    assert plain.held_layers is None


def test_the_config_goes_out_and_comes_back():
    cfg, _ = model()
    d = hf.hf_config_dict(cfg)
    assert d["model_type"] == "kimi_linear" and d["q_lora_rank"] is None
    assert d["held_layers"] == [1, 6, 7, 8]
    assert d["linear_attn_config"]["kda_layers"] == [1, 6, 7]
    assert d["linear_attn_config"]["full_attn_layers"] == [8]
    assert hf.config_from_hf(types.SimpleNamespace(**d)) == cfg
    share = {**HF_KEYS, "num_experts": 2, "num_routed_experts": 8,
             "expert_shard_count": 4, "expert_shard_index": 3}
    scfg = hf.config_from_hf(types.SimpleNamespace(**share))
    assert (scfg.moe.n_routed, scfg.moe.first_expert) == (8, 6)
    back = hf.hf_config_dict(scfg)
    assert (back["num_routed_experts"], back["expert_shard_index"]) == (8, 3)


@pytest.mark.parametrize("key,value,name", [
    ("num_expert_group", 2, "num_expert_group"),
    ("topk_group", 2, "topk_group"),
    ("moe_router_activation_func", "softmax", "moe_router_activation_func"),
    ("moe_layer_freq", 2, "moe_layer_freq"),
    ("mla_use_nope", False, "mla_use_nope"),
    ("rope_scaling", {"type": "yarn", "factor": 4.0}, "rope_scaling"),
    ("hidden_act", "gelu", "hidden_act"),
    ("num_key_value_heads", 2, "num_key_value_heads"),
])
def test_keys_of_the_family_that_are_not_built_are_refused_by_name(
        key, value, name):
    with pytest.raises(NotImplementedError, match=name):
        hf.config_from_hf(types.SimpleNamespace(**{**HF_KEYS, key: value}))


def test_parameter_count_at_the_published_widths():
    """``param_count`` of the benchmark's cut equals the sum of its
    leaves' sizes (shapes only) and the number in the configuration file;
    the mixers' sizes are ISSUE 63's reckoning."""
    from benchmark import kda_cost, weights

    with open(os.path.join(REPO, "benchmark", "configs",
                           "kimi-linear-48b-a3b.json")) as f:
        keys = json.load(f)
    cfg = weights.model_config(keys)
    assert cfg.layer_kinds == (DENSE, KDA, KDA, KDA, FULL)
    shapes = jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert n == transformer.param_count(cfg) == keys["n_parameters"]
    assert n == 602_434_432
    assert kda.kda_param_count(cfg.kda, 2304) == 39_514_272
    assert mla.mla_param_count(cfg.mla, 2304, 32) == 29_114_880
    assert kda_cost.layer_counts(keys) == {
        "kda": 4, "attn": 1, "dense": 1, "experts": 4}
    assert (kda_cost.runs(keys, True), kda_cost.runs(keys, False)) == (2, 1)
    assert keys["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size"]


def test_logprobs_match_the_reference():
    cfg, params = model()
    tok = tokens()
    want = jax.jit(ref.token_logprobs, static_argnums=1)(
        params, _frozen(HF_KEYS), tok)
    got = logprobs_of(system_logits(params, cfg, tok), tok)
    np.testing.assert_allclose(got, want, **TOL)


@pytest.mark.parametrize("remat", ["full", "matmuls"])
def test_loss_and_every_gradient_match_the_reference(remat):
    cfg, params = model()
    tok = tokens()
    loss, grads = jax.jit(jax.value_and_grad(lambda p: -jnp.mean(logprobs_of(
        system_logits(p, cfg, tok, remat=remat), tok))))(params)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, HF_KEYS, tok)))(params)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    got_g, want_g = hf.flatten_pytree(grads), hf.flatten_pytree(want)
    assert set(got_g) == set(want_g)
    for name in sorted(want_g):
        scale = float(jnp.abs(want_g[name]).max()) or 1.0
        np.testing.assert_allclose(
            got_g[name] / scale, want_g[name] / scale, atol=2e-4,
            err_msg=name)
    assert not np.any(got_g[f"layers/{KDA}/router_bias"])


@pytest.mark.parametrize("which", ref.WRONG)
def test_a_wrong_reference_is_told_apart(which):
    """Each named fault moves the reference's logprobs, on these weights,
    by more than the system differs from the right one (the state rounded
    to bfloat16 every 64 tokens needs a longer document than the others to
    show at all: its own row below)."""
    _, params = model()
    n = 150 if which == "state_bf16_each_chunk" else T
    tok = tokens(3, n)
    keys = _frozen(HF_KEYS)
    run = jax.jit(ref.token_logprobs, static_argnums=(1, 3))
    right = run(params, keys, tok, ref.NONE)
    wrong = run(params, keys, tok, frozenset({which}))
    floor = 3e-5 if which == "state_bf16_each_chunk" else 1e-3
    assert float(jnp.max(jnp.abs(wrong - right))) > floor, which


# ---- (b) packed rows ----

@pytest.mark.parametrize("lens,width", [((70, 83), 160), ((23, 64, 40), 128)])
def test_a_packed_row_of_documents_equals_each_alone(lens, width):
    """Documents behind one another in a row — starts inside a chunk of 64
    and on its grid, then padding — read what each reads alone: the state
    is zero, the taps read 0 and attention stops at a document's start."""
    cfg, params = model()
    row, seg, pos, docs = packed_row(lens, width)
    got = system_logits(params, cfg, row, seg, pos)[0]
    start = 0
    for doc in docs:
        want = ref.logits(params, HF_KEYS, doc)
        np.testing.assert_allclose(got[start:start + len(doc)], want, **TOL)
        start += len(doc)
    # ... and in the backward: the gradient of the second document's loss
    # does not reach the first document's embedding rows alone
    grads = jax.grad(lambda p: jnp.sum(system_logits(
        p, cfg, row, seg, pos)[0, lens[0]:lens[0] + lens[1]] ** 2))(params)
    assert all(bool(jnp.isfinite(g).all()) for g in jax.tree.leaves(grads))


@pytest.mark.parametrize("entry", ["full", "attention"])
def test_the_mixer_with_the_kernels_interpreted_equals_the_xla_form(entry):
    """At heads of 128 the mixer's kernel path (interpreted here: the
    fused entry, all heads at once, no checkpoint of its own) and its XLA
    form (a group of heads at a time under a checkpoint) give the same
    ``y`` and the same gradient of ``u`` and of every parameter, under
    either remat entry of the block around them."""
    from areal_tpu.models.config import KDAConfig

    cfg = KDAConfig(n_heads=4, head_dim=128)
    lp = jax.tree.map(lambda a: a[0], kda.init_kda_params(
        cfg, 1, 64, jax.random.PRNGKey(0), jnp.float32))
    lp = {k: v * 10 if v.ndim == 2 and k != "kda_conv" else v
          for k, v in lp.items()}
    u = jax.random.normal(jax.random.PRNGKey(1), (1, 160, 64))
    seg = jnp.asarray([[1] * 70 + [2] * 83 + [0] * 7], jnp.int32)
    norms = dict(kda.mixer_norm_counts())

    def run(impl):
        mixer = transformer._maybe_checkpoint(
            lambda u, lp: kda.kda_mixer(u, lp, cfg, 1e-5, seg, impl), entry)
        return jax.value_and_grad(lambda u, lp: jnp.sum(jnp.sin(
            mixer(u, lp))), argnums=(0, 1))(u, lp)

    (y, (du, dlp)), (y2, (du2, dlp2)) = run("pallas_interpret"), run(
        "reference")
    after = kda.mixer_norm_counts()
    assert all(after[k] > norms.get(k, 0) for k in ("kernel", "xla"))
    np.testing.assert_allclose(y, y2, rtol=1e-5)
    np.testing.assert_allclose(du, du2, atol=2e-5 * float(jnp.abs(du2).max()))
    assert set(dlp) == set(dlp2) == set(kda.param_shapes(cfg, 64))
    for name in dlp:
        np.testing.assert_allclose(
            dlp[name], dlp2[name],
            atol=5e-4 * (float(jnp.abs(dlp2[name]).max()) + 1e-6),
            err_msg=name)


# ---- (c) the share ----

def test_the_parts_all_the_shares_give_add_up_to_the_uncut_layer():
    """The routed parts of the four shares of two experts each, and the
    shared expert counted ONCE, add up to the uncut expert layer — in the
    program and in the reference (the guide's section 4)."""
    cfg, params = model()
    lp = {k: w[0] for k, w in params["layers"][KDA].items()}
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 24, 32))
    whole, aux = moe.moe_mlp(x, lp, cfg.moe)
    assert float(aux["dropped_frac"]) == 0.0
    shared = ref.swiglu(x, lp["s_gate"], lp["s_up"], lp["s_down"])
    parts = []
    for shard in range(4):
        share = dataclasses.replace(
            cfg.moe, num_experts=2, router_experts=8, first_expert=2 * shard)
        held = {**lp, **{k: lp[k][2 * shard:2 * shard + 2]
                         for k in ("e_gate", "e_up", "e_down")}}
        y, _ = moe.moe_mlp(x, held, share)
        parts.append(y - shared)  # every share adds the shared expert whole
        keys = {**HF_KEYS, "num_experts": 2, "num_routed_experts": 8,
                "expert_shard_count": 4, "expert_shard_index": shard}
        np.testing.assert_allclose(y[0], ref.moe(x[0], keys, held), **TOL)
        np.testing.assert_allclose(parts[-1][0],
                                   ref.routed(x[0], keys, held), **TOL)
    np.testing.assert_allclose((sum(parts) + shared)[0], whole[0], **TOL)
    np.testing.assert_allclose(ref.moe(x[0], HF_KEYS, lp), whole[0], **TOL)


# ---- (d) weights in the publisher's names ----

def test_hf_names_round_trip():
    cfg, params = model()
    sd = hf.params_to_hf_state_dict(params, cfg)
    # published block numbers, 0-based: blocks 1, 6, 7, 8
    assert {k.split(".")[2] for k in sd if k.startswith("model.layers.")} == {
        "0", "5", "6", "7"}
    for name, shape in {
            "model.layers.5.self_attn.q_proj.weight": (32, 32),
            "model.layers.5.self_attn.k_conv1d.weight": (32, 1, 4),
            "model.layers.5.self_attn.A_log": (1, 1, 4, 1),
            "model.layers.5.self_attn.dt_bias": (32,),
            "model.layers.5.self_attn.f_a_proj.weight": (8, 32),
            "model.layers.5.self_attn.f_b_proj.weight": (32, 8),
            "model.layers.5.self_attn.g_a_proj.weight": (8, 32),
            "model.layers.5.self_attn.g_b_proj.weight": (32, 8),
            "model.layers.5.self_attn.b_proj.weight": (4, 32),
            "model.layers.5.self_attn.o_norm.weight": (8,),
            "model.layers.5.self_attn.o_proj.weight": (32, 32),
            "model.layers.7.self_attn.q_proj.weight": (64, 32),
            "model.layers.7.self_attn.kv_a_proj_with_mqa.weight": (12, 32),
            "model.layers.7.self_attn.kv_b_proj.weight": (80, 8),
            "model.layers.7.self_attn.o_proj.weight": (32, 32),
            "model.layers.0.mlp.gate_proj.weight": (48, 32),
            "model.layers.5.block_sparse_moe.gate.weight": (8, 32),
            "model.layers.5.block_sparse_moe.gate.e_score_correction_bias":
                (8,),
            "model.layers.5.block_sparse_moe.experts.7.w2.weight": (32, 24),
            "model.layers.5.block_sparse_moe.shared_experts.up_proj.weight":
                (24, 32)}.items():
        assert sd[name].shape == shape, name
    back = hf.params_from_hf_state_dict(sd, cfg)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(params),
                            jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), b, err_msg=str(path))


# ---- (e) where the block goes, and where it is refused by name ----

@pytest.mark.parametrize("where", ["generate", "pipeline", "ring", "specs"])
def test_where_the_block_goes_and_where_it_is_refused_by_name(where):
    cfg, params = model()
    if where == "generate":
        from areal_tpu.models import generate

        assert generate.decode_refusal(cfg).startswith(
            "channel_decay_rule_decode_state")
        with pytest.raises(NotImplementedError,
                           match="channel_decay_rule_decode_state"):
            transformer.forward(params, cfg, tokens()[None],
                                jnp.arange(T)[None],
                                segment_ids=jnp.ones((1, T), jnp.int32))
    elif where == "pipeline":
        from jax.sharding import Mesh

        from areal_tpu.parallel import pipeline

        mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("pp",))
        pipeline._WARNED_FALLBACKS.discard("channel_decay_rule")
        assert pipeline.pick_pp_microbatches(mesh, cfg, 4) is None
        assert "channel_decay_rule" in pipeline._WARNED_FALLBACKS
    elif where == "ring":
        from areal_tpu.parallel import ring

        assert ring.ring_refusal(cfg) == "channel_decay_rule"
        assert "channel_decay_rule" in ring.RING_REFUSALS
    else:
        from jax.sharding import PartitionSpec as P

        from areal_tpu.parallel.sharding import param_partition_specs

        specs = param_partition_specs(cfg)
        assert jax.tree.structure(jax.tree.map(
            lambda s: 0, specs, is_leaf=lambda x: isinstance(x, P))
        ) == jax.tree.structure(jax.tree.map(lambda a: 0, params))
        for s, a in zip(
                jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)),
                jax.tree.leaves(params)):
            assert len(s) == a.ndim
        assert specs["layers"][FULL]["wq"][2] == "tp"
        assert specs["layers"][KDA]["kda_qkv"][2] is None  # heads whole


# ---- (f) what the benchmark and the operator read ----

def test_the_scopes_and_counts_the_benchmark_reads_are_the_programs():
    from areal_tpu.base import telemetry
    from benchmark import kda_cost, kimi_trace

    assert kimi_trace.KDA_SCOPES == telemetry.KDA_SCOPES
    cfg, params = model()
    rules, paths = dict(kda.geometry_counts()), dict(mla.geometry_counts())
    text = system_logits.lower(params, cfg, tokens(1, 31)).as_text(
        debug_info=True)
    for scope in telemetry.KDA_SCOPES + telemetry.MLA_SCOPES + (
            "attention", "o_proj", "moe_router", "moe_experts",
            "shared_expert", "mlp"):
        assert scope in text, scope
    assert "qkv_proj" not in text and "rope" not in text.replace(
        "rope_", "")  # nothing is rotated
    # one rule a run of KDA blocks a program (block 1's, the expert
    # blocks'), one assembly for the attention block, keyed with the
    # absent query latent (0) and both widths
    key = (1, 31, 64, 4, 8, 8)
    assert kda.geometry_counts()[key] - rules.get(key, 0) == 2
    assert key[2:] == (kda_cost.CHUNK,) + kda_cost.rule_geometry(HF_KEYS)[1:]
    path = (1, 31) + kda_cost.mla_geometry(HF_KEYS)
    assert path[2:] == (4, 0, 8, 12, 4, 8)
    assert mla.geometry_counts()[path] - paths.get(path, 0) == 1


def test_the_live_flop_count_counts_the_mixers():
    from areal_tpu.base import monitor

    cfg, _ = model()
    dense = dataclasses.replace(cfg, kda=None, layer_types=None,
                                mlp_layer_types=None)
    assert monitor._kda_flops(dense, 100.0) == 0.0
    got = monitor.model_flops_per_token(cfg, 100.0, backward=False)
    assert got > 0 and np.isfinite(got)
    # three KDA mixers' matrices are in it
    assert monitor._kda_flops(cfg, 100.0) != 0.0


def test_the_gauges_of_the_train_step(monkeypatch):
    """``train/kda_resets_in_chunk_per_row`` counts document starts off
    the 64-token grid a packed row; ``train/kda_kernel_frac`` the share of
    the traced rules that took the kernels."""
    from areal_tpu.backend import microbatch as mbu

    class Layout:
        def __init__(self, shape, placements):
            self.shape, self.placements = shape, placements

    class MB:
        def __init__(self, layout):
            self.layout = layout

    mbs = [MB(Layout((1, 256), [(0, 0), (0, 70), (0, 128)])),
           MB(Layout((1, 256), [(0, 0), (0, 100)]))]
    assert mbu.resets_in_chunk_per_row(mbs, 64) == 1.0
    frac = kda.rule_kernel_frac()
    assert frac is None or 0.0 <= frac <= 1.0


def test_where_the_mixers_ends_ran_is_a_gauge_of_the_train_step():
    """``train/kda_kernel_frac`` and ``train/kda_norms_in_kernel_frac``: of
    the rules and the mixers traced, the share on the Pallas kernel pair
    and the share whose ends ran inside it — gauges and attributes of the
    ``train/fwd_bwd`` span (the tiny model is off the kernels' lane grid:
    its own mixers count ``xla``)."""
    from areal_tpu.api.data import MicroBatchSpec, SequenceSample
    from areal_tpu.api.model import FinetuneSpec
    from areal_tpu.api.train_config import OptimizerConfig, TelemetryConfig
    from areal_tpu.backend.jax_train import JaxTrainEngine
    from areal_tpu.base import telemetry

    cfg, params = model()
    eng = JaxTrainEngine(cfg, params, OptimizerConfig(type="sgd", lr=1e-2),
                         FinetuneSpec(1, 8, 4), compute_dtype="float32",
                         length_bucket=16, rows_bucket=1, seqs_bucket=4)
    lens = [9, 12, 7, 14]
    rng = np.random.RandomState(0)
    sample = SequenceSample.from_default(
        ids=[f"s{i}" for i in range(len(lens))],
        data={"packed_input_ids": rng.randint(
            2, 97, sum(lens)).astype(np.int32),
            "loss_mask": np.ones(sum(lens), np.float32)},
        seqlens=lens)

    def sq_loss(logits, batch):
        w = (batch["segment_ids"] > 0).astype(jnp.float32)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return jnp.sum(jnp.sum(lp * lp, axis=-1) * w), {"n": jnp.sum(w)}

    before = kda.mixer_norm_counts().get("xla", 0)
    telemetry.configure("t", "t", "trainer", 0,
                        TelemetryConfig(enabled=True), push=False)
    try:
        for _ in range(2):  # the first step traces; the second reads
            eng.train_batch(sample, MicroBatchSpec(max_tokens_per_mb=48),
                            sq_loss, lambda mb: mb.n_tokens)
        snap = telemetry.get().snapshot()
    finally:
        telemetry.shutdown()
    assert kda.mixer_norm_counts()["xla"] > before
    gauges = snap["gauges"]
    assert gauges["train/kda_kernel_frac"] == kda.rule_kernel_frac()
    assert gauges["train/kda_norms_in_kernel_frac"] == pytest.approx(
        kda.norms_in_kernel_frac())
    assert 0.0 <= gauges["train/kda_norms_in_kernel_frac"] < 1.0
    spans = [s for s in snap["spans"] if s["name"] == "train/fwd_bwd"]
    assert spans[-1]["attrs"]["kda_norms_in_kernel_frac"] == gauges[
        "train/kda_norms_in_kernel_frac"]
