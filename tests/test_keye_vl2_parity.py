"""Keye-VL-2.0's language model (``model_type`` KeyeVL2) through the system
against the benchmark's plain reference (``benchmark/reference_keye_vl2.py``:
float32, the indexer's scores as a [queries, L] array, ``lax.top_k`` a
query, attention under the boolean mask, every held expert on every token,
one document at a time) on seeded weights, on the CPU at a tiny size:
hidden 32, 4 / 2 heads of 8 with a per-head q / k norm, an indexer of 4
heads of 8 over one key head that keeps the 16 best keys of documents of
29-120 tokens (so most queries really select), 8 experts of 24 (3 a token,
renormalised), 3 blocks.

Both sides compute in float32 here, so they differ by the order of float32
sums only: a pair on the threshold could flip between them, which drawn
float32 scores make a measure-zero event (the selected-set tests hold the
sets EQUAL); every fault ``reference.WRONG`` names moves logprobs by far
more than the tolerance.
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

from areal_tpu.models import dsa, hf, moe, transformer
from areal_tpu.models.config import FULL
from benchmark import reference_keye_vl2 as ref

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HF_KEYS = {
    "model_type": "KeyeVL2", "num_hidden_layers": 3, "hidden_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "intermediate_size": 96, "moe_intermediate_size": 24, "vocab_size": 67,
    "rms_norm_eps": 1e-6, "rope_theta": 10000000,
    "rope_scaling": {"mrope_section": [1, 1, 2], "rope_type": "default",
                     "type": "default"},
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 4,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 16},
    "num_experts": 8, "num_local_experts": 8, "num_experts_per_tok": 3,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [],
    "attention_bias": False, "sliding_window": None,
    "use_sliding_window": False, "max_window_layers": 3,
    "tie_word_embeddings": False, "hidden_act": "silu",
    "max_position_embeddings": 4096,
}
TOL = dict(atol=3e-4, rtol=3e-4)
NORMS = ("ln1", "ln2", "final_ln", "q_norm", "k_norm")
T = 61


class _frozen(dict):
    """The HF keys as a static (hashable) argument of ``jax.jit``."""

    def __hash__(self):
        return hash(json.dumps(self, sort_keys=True))


@functools.lru_cache(maxsize=None)
def model(seed=0, scale=0.3):
    """(config, float32 params): init_params with the matrices scaled up
    (so that every gate, index score and expert matters), the norm weights
    random around 1 and the key LayerNorm's bias drawn."""
    cfg = hf.config_from_hf(types.SimpleNamespace(**HF_KEYS))

    @jax.jit
    def build():
        flat = hf.flatten_pytree(
            transformer.init_params(cfg, jax.random.PRNGKey(seed)))
        rngs = jax.random.split(jax.random.PRNGKey(seed + 1), len(flat))
        for (name, x), k in zip(sorted(flat.items()), rngs):
            leaf = name.split("/")[-1]
            if leaf in NORMS or name.endswith("indexer/k_norm"):
                flat[name] = 1.0 + 0.1 * jax.random.normal(k, x.shape)
            elif leaf == "k_norm_b":
                flat[name] = 0.1 * jax.random.normal(k, x.shape)
            elif leaf == "embedding":
                flat[name] = x * 40.0
            else:
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
    assert cfg.layer_kinds == (FULL,) * 3 and cfg.use_qk_norm
    assert cfg.dsa == dsa.SparseAttnConfig(4, 8, 16, 512, 512)
    assert cfg.has_cacheless_layers and not cfg.is_hybrid
    assert cfg.moe.capacity_factor is None and cfg.moe.aux_loss_coeff == 0.0
    assert set(params["layers"][dsa.INDEXER]) == {
        "wq", "wk", "ww", "k_norm", "k_norm_b"}
    assert params["layers"][dsa.INDEXER]["wq"].shape == (3, 32, 32)
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    assert n == transformer.param_count(cfg)


def test_the_config_goes_out_and_comes_back():
    cfg, _ = model()
    d = hf.hf_config_dict(cfg)
    assert d["model_type"] == "KeyeVL2"
    assert d["sa_config"] == HF_KEYS["sa_config"]
    assert d["rope_scaling"] == HF_KEYS["rope_scaling"]
    assert hf.config_from_hf(types.SimpleNamespace(**d)) == cfg
    share = {**HF_KEYS, "num_experts": 2, "num_routed_experts": 8,
             "expert_shard_count": 4, "expert_shard_index": 3}
    scfg = hf.config_from_hf(types.SimpleNamespace(**share))
    assert (scfg.moe.n_routed, scfg.moe.first_expert) == (8, 6)
    back = hf.hf_config_dict(scfg)
    assert (back["num_routed_experts"], back["expert_shard_index"]) == (8, 3)
    # the native checkpoint's config: asdict through JSON and back
    assert hf.config_from_dict(json.loads(json.dumps(
        dataclasses.asdict(cfg)))) == cfg


@pytest.mark.parametrize("key,value,name", [
    ("sliding_window", 4096, "sliding_window"),
    ("use_sliding_window", True, "use_sliding_window"),
    ("rope_scaling", {"rope_type": "yarn", "factor": 4.0,
                      "mrope_section": [1, 1, 2]}, "rope_type"),
    ("sa_config", {**HF_KEYS["sa_config"], "indexer_num_kv_heads": 2},
     "indexer_num_kv_heads"),
    ("mlp_only_layers", [0], "mlp_only_layers"),
])
def test_keys_of_the_family_that_are_not_built_are_refused_by_name(
        key, value, name):
    with pytest.raises(NotImplementedError, match=name):
        hf.config_from_hf(types.SimpleNamespace(**{**HF_KEYS, key: value}))


def test_unequal_mrope_streams_are_refused_by_name():
    text = np.broadcast_to(np.arange(7), (3, 2, 7))
    np.testing.assert_array_equal(hf.mrope_positions(text), text[0])
    patch = text.copy()
    patch[1, 0, 3] += 1  # an image patch's height differs from its time
    with pytest.raises(NotImplementedError, match="mrope_unequal_streams"):
        hf.mrope_positions(patch)
    with pytest.raises(ValueError, match="mrope_section"):
        hf.config_from_hf(types.SimpleNamespace(**{
            **HF_KEYS, "rope_scaling": {"mrope_section": [1, 1, 1],
                                        "rope_type": "default"}}))


def test_parameter_count_at_the_published_widths():
    """``param_count`` of the benchmark's cut equals the sum of its
    leaves' sizes (shapes only) and the number in the configuration file;
    a block's parts are ISSUE 66's reckoning."""
    from benchmark import weights

    with open(os.path.join(REPO, "benchmark", "configs",
                           "keye-vl-2.0-30b-a3b.json")) as f:
        keys = json.load(f)
    cfg = weights.model_config(keys)
    shapes = jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert n == transformer.param_count(cfg) == keys["n_parameters"]
    assert n == 432_697_600
    assert dsa.indexer_param_count(cfg.dsa, 2048) == 2_261_120
    assert transformer._block_param_count(cfg, False) == 59_150_720
    assert (cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim) == (32, 4, 128)
    assert (cfg.dsa.n_heads, cfg.dsa.head_dim, cfg.dsa.top_k) == (16, 64, 2048)
    assert (cfg.moe.n_routed, cfg.moe.num_experts, cfg.moe.top_k) == (128, 8, 8)
    assert keys["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size"]
    whole = dataclasses.replace(
        cfg, n_layers=48, vocab_size=151936,
        moe=dataclasses.replace(cfg.moe, num_experts=128, router_experts=None))
    assert 30.5e9 < transformer.param_count(whole) < 30.7e9


# ---- (b) against the reference ----

def test_logprobs_match_the_reference():
    cfg, params = model()
    tok = tokens()
    want = jax.jit(ref.token_logprobs, static_argnums=1)(
        params, _frozen(HF_KEYS), tok)
    got = logprobs_of(system_logits(params, cfg, tok), tok)
    np.testing.assert_allclose(got, want, **TOL)


@pytest.mark.parametrize("remat", ["full", "attention", "matmuls"])
def test_loss_and_every_gradient_match_the_reference(remat):
    """Every trainable gradient within float32 rounding of the
    reference's ``jax.grad``; the indexer's EXACTLY zero on both sides."""
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
        if f"/{dsa.INDEXER}/" in name:
            assert not np.any(np.asarray(got_g[name])), name
            assert not np.any(np.asarray(want_g[name])), name
            continue
        scale = float(jnp.abs(want_g[name]).max()) or 1.0
        np.testing.assert_allclose(
            got_g[name] / scale, want_g[name] / scale, atol=2e-4,
            err_msg=name)


@pytest.mark.parametrize("which", ref.WRONG)
def test_a_wrong_reference_is_told_apart(which):
    """Each named fault moves the reference's logprobs, on these weights,
    by more than the system differs from the right one."""
    _, params = model()
    tok = tokens(3, 100)
    keys = _frozen(HF_KEYS)
    run = jax.jit(ref.token_logprobs, static_argnums=(1, 3))
    right = run(params, keys, tok, ref.NONE)
    wrong = run(params, keys, tok, frozenset({which}))
    assert float(jnp.max(jnp.abs(wrong - right))) > 1e-3, which


@pytest.mark.parametrize("lens,width", [((70, 83), 160), ((23, 120, 40), 256),
                                        ((200,), 200)])
def test_the_selected_set_equals_the_references_pair_for_pair(lens, width):
    """The program's selection on a packed row — its ``tau`` / ``cut`` a
    query turned into a mask — equals the reference's ``top_k`` of each
    document alone, pair for pair, and ``|S_t| = min(p + 1, top_k)``."""
    cfg, params = model()
    row, seg, pos, docs = packed_row(lens, width)
    lp = jax.tree.map(lambda w: w[0], params["layers"])
    h = params["embedding"][row]
    x = transformer.rms_norm(h, lp["ln1"], cfg.rms_norm_eps)
    qi, ki, w = dsa.index_inputs(x, lp[dsa.INDEXER], cfg.dsa, pos,
                                 cfg.rope_of(FULL))
    scores = dsa.scores_xla(qi, ki, w, cfg.dsa.n_heads)
    meta = dsa.select_xla(scores, seg, cfg.dsa.top_k)
    mask = np.asarray(dsa.mask_xla(scores, meta, seg))[0]
    at = 0
    for doc in docs:
        n = len(doc)
        u = ref.rms(ref.f32(params["embedding"][doc]), lp["ln1"],
                    cfg.rms_norm_eps)
        want = np.asarray(ref.selection(u, HF_KEYS, lp))
        np.testing.assert_array_equal(mask[at:at + n, at:at + n], want)
        assert not mask[at:at + n, :at].any()  # nothing of another document
        assert not mask[at:at + n, at + n:].any()
        np.testing.assert_array_equal(
            mask[at:at + n].sum(-1), np.minimum(np.arange(n) + 1, 16))
        at += n
    assert not mask[at:].any()  # a padding query selects nothing


@pytest.mark.parametrize("lens,width", [((70, 83), 160), ((23, 64, 40), 128)])
def test_a_packed_row_of_documents_equals_each_alone(lens, width):
    """Two and three documents a row: each one's logits equal the
    document's alone (the selection ranks its own document's keys only),
    and the reference's."""
    cfg, params = model()
    row, seg, pos, docs = packed_row(lens, width)
    got = system_logits(params, cfg, row, seg, pos)[0]
    at = 0
    for doc in docs:
        alone = system_logits(params, cfg, doc)
        np.testing.assert_allclose(got[at:at + len(doc)], alone, **TOL)
        want = jax.jit(ref.logits, static_argnums=1)(
            params, _frozen(HF_KEYS), doc)
        np.testing.assert_allclose(alone, want, **TOL)
        at += len(doc)


@pytest.mark.parametrize("top_k", [200, 61])
def test_below_top_k_the_layer_is_full_causal_attention(top_k):
    """Where no document is longer than ``top_k`` (and at top_k = L) the
    model IS qwen3_moe with the indexer's weights ignored."""
    cfg, params = model()
    tok = tokens()
    wide = dataclasses.replace(
        cfg, dsa=dataclasses.replace(cfg.dsa, top_k=top_k))
    plain = dataclasses.replace(cfg, dsa=None)
    layers = {k: v for k, v in params["layers"].items() if k != dsa.INDEXER}
    want = system_logits({**params, "layers": layers}, plain, tok)
    np.testing.assert_allclose(system_logits(params, wide, tok), want,
                               atol=1e-5, rtol=1e-5)
    narrow = system_logits(params, cfg, tok)
    assert float(jnp.abs(narrow - want).max()) > 1e-2  # top-k 16 selects


# ---- (c) the share ----

def test_the_parts_all_the_shares_give_add_up_to_the_uncut_layer():
    """The routed parts of the four shares of two experts each add up to
    the uncut expert layer — in the program and in the reference (the
    guide's section 4)."""
    cfg, params = model()
    lp = {k: w[0] for k, w in params["layers"].items() if k != dsa.INDEXER}
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 24, 32))
    whole, aux = moe.moe_mlp(x, lp, cfg.moe)
    assert float(aux["dropped_frac"]) == 0.0
    parts = []
    for shard in range(4):
        share = dataclasses.replace(
            cfg.moe, num_experts=2, router_experts=8, first_expert=2 * shard)
        held = {**lp, **{k: lp[k][2 * shard:2 * shard + 2]
                         for k in ("e_gate", "e_up", "e_down")}}
        y, _ = moe.moe_mlp(x, held, share)
        parts.append(y)
        keys = {**HF_KEYS, "num_experts": 2, "num_routed_experts": 8,
                "expert_shard_count": 4, "expert_shard_index": shard}
        np.testing.assert_allclose(y[0], ref.moe(x[0], keys, held), **TOL)
    np.testing.assert_allclose(sum(parts)[0], whole[0], **TOL)
    np.testing.assert_allclose(ref.moe(x[0], HF_KEYS, lp), whole[0], **TOL)


# ---- (d) weights in the publisher's names ----

def test_hf_names_round_trip():
    cfg, params = model()
    sd = hf.params_to_hf_state_dict(params, cfg)
    for name, shape in {
            "model.layers.1.self_attn.q_proj.weight": (32, 32),
            "model.layers.1.self_attn.k_proj.weight": (16, 32),
            "model.layers.1.self_attn.q_norm.weight": (8,),
            "model.layers.1.self_attn.indexer.wq.weight": (32, 32),
            "model.layers.1.self_attn.indexer.wk.weight": (8, 32),
            "model.layers.1.self_attn.indexer.weights_proj.weight": (4, 32),
            "model.layers.1.self_attn.indexer.k_norm.weight": (8,),
            "model.layers.1.self_attn.indexer.k_norm.bias": (8,),
            "model.layers.2.mlp.gate.weight": (8, 32),
            "model.layers.2.mlp.experts.7.down_proj.weight": (32, 24),
            "lm_head.weight": (67, 32)}.items():
        assert sd[name].shape == shape, name
    back = hf.params_from_hf_state_dict(sd, cfg)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(params),
                            jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), b, err_msg=str(path))
    assert set(hf.flatten_pytree(back)) == set(hf.flatten_pytree(params))


# ---- (e) where the block goes, and where it is refused by name ----

@pytest.mark.parametrize("where", ["generate", "pipeline", "ring", "specs"])
def test_where_the_block_goes_and_where_it_is_refused_by_name(where):
    cfg, params = model()
    if where == "generate":
        from areal_tpu.models import generate

        assert generate.decode_refusal(cfg).startswith(
            "sparse_attention_indexer_cache")
        with pytest.raises(NotImplementedError,
                           match="sparse_attention_indexer_cache"):
            transformer.forward(params, cfg, tokens()[None],
                                jnp.arange(T)[None],
                                segment_ids=jnp.ones((1, T), jnp.int32))
    elif where == "pipeline":
        from jax.sharding import Mesh

        from areal_tpu.parallel import pipeline

        mesh = Mesh(np.asarray(jax.devices()[:3]).reshape(3), ("pp",))
        pipeline._WARNED_FALLBACKS.discard(dsa.PIPELINE_REFUSAL)
        assert pipeline.pick_pp_microbatches(mesh, cfg, 6) is None
        assert dsa.PIPELINE_REFUSAL in pipeline._WARNED_FALLBACKS
        assert dsa.PIPELINE_REFUSAL in pipeline._FALLBACK_HINTS
    elif where == "ring":
        from jax.sharding import Mesh

        from areal_tpu.parallel import ring

        assert ring.ring_refusal(cfg) == dsa.RING_REFUSAL
        assert dsa.RING_REFUSAL in ring.RING_REFUSALS
        mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("sp",))
        assert not ring.ring_eligible(mesh, cfg, 2, 64)
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
        assert specs["layers"]["wq"][2] == "tp"
        assert specs["layers"][dsa.INDEXER]["wq"][2] is None  # heads whole
