"""Mellum 2 through the system against the benchmark's plain reference
(``benchmark/reference_mellum2.py``: float32, no kernel, no scan, every
held expert on every token) on seeded weights, on the CPU at a tiny size:
hidden 64, 4 query / 2 key-value heads of 16, window 8, 8 experts of which
a token takes 2, 8 layers = two periods of (sliding, sliding, sliding,
full), YaRN on the full layers with the published factor, betas and
original context.

Both sides compute in float32 here, so they differ by the order of float32
sums only: 2e-4 on logits of order 1 (tests/test_olmoe_parity.py). A
window left off, a plain RoPE table on the full layers or gates that are
not renormalised move logits by 1e-2 and more on these weights.
"""

import hashlib
import math
import functools
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import generate as gen
from areal_tpu.models import hf, moe as moemod, transformer
from areal_tpu.models.config import FULL, SLIDING, MoEConfig, RopeConfig
from benchmark import reference_mellum2 as ref

YARN = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}
HF_KEYS = {
    "model_type": "mellum", "num_hidden_layers": 8, "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "vocab_size": 97, "num_experts": 8, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False, "max_position_embeddings": 131072,
    "sliding_window": 8, "use_sliding_window": True,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"]
    + ["sliding_attention"] * 3 + ["full_attention"],
    "rope_parameters": {
        "full_attention": YARN,
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
    },
}
# one rank's share of the same model: experts 2 and 3 of the 8
SHARE_KEYS = {**HF_KEYS, "num_experts": 2, "num_routed_experts": 8,
              "expert_shard_count": 4, "expert_shard_index": 1}
TOL = dict(atol=2e-4, rtol=2e-4)
NORMS = ("ln1", "ln2", "final_ln")


def model(keys, seed=0, scale=0.3):
    """(config, float32 params): init_params with the matrices scaled up
    (so that attention and the experts matter) and the norm weights random
    around 1. Built once a set of keys: no test writes into the tree it
    gets."""
    return _model(json.dumps(keys, sort_keys=True), seed, scale)


@functools.lru_cache(maxsize=None)
def _model(keys, seed, scale):
    cfg = hf.config_from_hf(types.SimpleNamespace(**json.loads(keys)))
    flat = hf.flatten_pytree(
        transformer.init_params(cfg, jax.random.PRNGKey(seed)))
    rngs = jax.random.split(jax.random.PRNGKey(seed + 1), len(flat))
    for (name, x), k in zip(sorted(flat.items()), rngs):
        flat[name] = (1.0 + 0.1 * jax.random.normal(k, x.shape)
                      if name.split("/")[-1] in NORMS else x * (scale / 0.02))
    return cfg, hf.unflatten_pytree(flat)


def tokens(seed=0, T=40):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(2, HF_KEYS["vocab_size"], T), jnp.int32)


def system_logits(params, cfg, tok):
    T = tok.shape[0]
    out, _ = transformer.forward(
        params, cfg, tok[None], jnp.arange(T, dtype=jnp.int32)[None],
        segment_ids=jnp.ones((1, T), jnp.int32), attn_impl="reference",
        return_kv=False)
    return out[0]


def mean_logprob(logits, tok):
    lp = jax.nn.log_softmax(logits[:-1], -1)
    return jnp.mean(jnp.take_along_axis(lp, tok[1:, None], -1))


KEYS = {"whole": HF_KEYS, "share": SHARE_KEYS}
# one period of the pattern (three sliding blocks, one full): what the
# gradients and a decode through the cache need of it
ONE_PERIOD = {which: {**keys, "num_hidden_layers": 4,
                      "layer_types": keys["layer_types"][:4]}
              for which, keys in KEYS.items()}


# ---- (a) the program against the reference ----

@pytest.mark.parametrize("which", sorted(KEYS))
def test_the_family_reads_the_pattern_both_ropes_and_the_share(which):
    cfg, _ = model(KEYS[which])
    assert cfg.layer_kinds == (SLIDING,) * 3 + (FULL,) + (SLIDING,) * 3 + (
        FULL,)
    assert cfg.period_kinds == (SLIDING, SLIDING, SLIDING, FULL)
    assert cfg.window_of(SLIDING) == 8 and cfg.window_of(FULL) is None
    assert cfg.rope_of(SLIDING) == RopeConfig(base=500000.0)
    assert cfg.rope_of(FULL).factor == 16 and cfg.rope_of(FULL).scale == (
        pytest.approx(0.1 * math.log(16) + 1))
    assert cfg.moe.capacity_factor is None and cfg.moe.norm_topk_prob
    assert cfg.moe.n_routed == 8
    assert (cfg.moe.num_experts, cfg.moe.first_expert, cfg.moe.is_share) == (
        (8, 0, False) if which == "whole" else (2, 2, True))
    # and back: the config.json the family writes reads to the same config
    again = hf.config_from_hf(types.SimpleNamespace(**hf.hf_config_dict(cfg)))
    assert again == cfg


@pytest.mark.parametrize("which", sorted(KEYS))
def test_logits_match_the_reference(which):
    cfg, params = model(KEYS[which])
    tok = tokens()
    np.testing.assert_allclose(system_logits(params, cfg, tok),
                               ref.logits(params, KEYS[which], tok), **TOL)


@pytest.mark.parametrize("which", sorted(KEYS))
def test_loss_and_gradients_match_the_reference(which):
    keys = ONE_PERIOD[which]
    cfg, params = model(keys)
    tok = tokens(1)
    got_l, got_g = jax.jit(jax.value_and_grad(
        lambda p: mean_logprob(system_logits(p, cfg, tok), tok)))(params)
    want_l, want_g = jax.jit(jax.value_and_grad(
        lambda p: mean_logprob(ref.logits(p, keys, tok), tok)))(params)
    assert float(got_l) == pytest.approx(float(want_l), abs=1e-5)
    got_g, want_g = hf.flatten_pytree(got_g), hf.flatten_pytree(want_g)
    assert sorted(got_g) == sorted(want_g)
    for name in got_g:  # float32 sums in another order: 2e-4 of the largest
        scale = float(jnp.max(jnp.abs(want_g[name])))
        np.testing.assert_allclose(got_g[name], want_g[name],
                                   atol=2e-4 * scale, rtol=2e-3, err_msg=name)


WRONG = {
    "no_window": {"sliding_window": 10 ** 6},
    "no_yarn": {"rope_parameters": {
        **HF_KEYS["rope_parameters"],
        "full_attention": HF_KEYS["rope_parameters"]["sliding_attention"]}},
    "gates_not_renormalised": {"norm_topk_prob": False},
}


@pytest.mark.parametrize("variant", sorted(WRONG))
def test_a_wrong_model_is_far_outside_the_tolerance(variant):
    """What the tolerance is FOR: each of the model's mechanisms, left out
    of the reference, moves the logits by far more than it allows."""
    cfg, params = model(HF_KEYS)
    tok = tokens()
    got = system_logits(params, cfg, tok)
    wrong = ref.logits(params, {**HF_KEYS, **WRONG[variant]}, tok)
    assert float(jnp.max(jnp.abs(got - wrong))) > 50 * TOL["atol"]


# ---- (b) the shares of one expert layer ----

def expert_layer(seed=0, D=32, F=16, E=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    lp = {"router": jax.random.normal(ks[0], (D, E)) * 0.5,
          "e_gate": jax.random.normal(ks[1], (E, D, F)) * 0.3,
          "e_up": jax.random.normal(ks[2], (E, D, F)) * 0.3,
          "e_down": jax.random.normal(ks[3], (E, F, D)) * 0.3}
    return lp, jax.random.normal(ks[4], (2, 12, D))


@pytest.mark.parametrize("renorm", [True, False], ids=["renorm", "as_is"])
@pytest.mark.parametrize("shares", [2, 4])
def test_the_shares_of_a_layer_add_up_to_the_uncut_reference(shares, renorm):
    """Each share routes over all 8 experts, normalises the gates over
    all the chosen ones and adds only its held experts' part: the parts
    sum to the whole layer as the reference computes it, and the pairs
    that landed on the shares to the pairs routed."""
    lp, x = expert_layer()
    E, k = 8, 3
    held = E // shares
    cut = {"num_experts": E, "num_experts_per_tok": k,
           "norm_topk_prob": renorm}
    want = ref.moe(x.reshape(-1, x.shape[-1]), cut, lp["router"],
                   lp["e_gate"], lp["e_up"], lp["e_down"])
    total, landed = 0.0, 0.0
    for i in range(shares):
        moe = MoEConfig(num_experts=held, top_k=k, capacity_factor=None,
                        norm_topk_prob=renorm, router_experts=E,
                        first_expert=i * held)
        mine = {"router": lp["router"], **{
            n: lp[n][i * held:(i + 1) * held]
            for n in ("e_gate", "e_up", "e_down")}}
        y, aux = moemod.moe_mlp(x, mine, moe)
        assert float(aux["dropped_frac"]) == 0.0
        assert float(aux["routed_rows"]) == x.shape[0] * x.shape[1] * k
        assert aux["expert_load"].shape == (E,)
        # the share against the reference given the same share
        part = ref.moe(
            x.reshape(-1, x.shape[-1]),
            {**cut, "num_experts": held, "expert_shard_index": i},
            mine["router"], mine["e_gate"], mine["e_up"], mine["e_down"])
        np.testing.assert_allclose(y.reshape(part.shape), part, **TOL)
        total, landed = total + y, landed + float(aux["local_rows"])
    np.testing.assert_allclose(total.reshape(want.shape), want, **TOL)
    assert landed == x.shape[0] * x.shape[1] * k


def test_a_share_masks_padding_and_sets_up_no_collective():
    lp, x = expert_layer(1)
    moe = MoEConfig(num_experts=2, top_k=2, capacity_factor=None,
                    router_experts=8, first_expert=4)
    mine = {"router": lp["router"], **{n: lp[n][4:6] for n in
                                       ("e_gate", "e_up", "e_down")}}
    mask = jnp.ones(x.shape[:2], bool).at[1, 6:].set(False)
    y, aux = moemod.moe_mlp(x, mine, moe, mask=mask)
    assert float(aux["routed_rows"]) == 18 * 2
    assert float(jnp.abs(y[1, 6:]).max()) == 0.0
    text = jax.jit(lambda x: moemod.moe_mlp(x, mine, moe, mask=mask)[0]
                   ).lower(x).as_text()
    assert "all_gather" not in text and "all_reduce" not in text
    assert not moemod.ep_eligible(None, moe, 2, 12)


# ---- (d) YaRN by hand ----

def test_yarn_frequencies_by_hand():
    """Published sizes: heads of 128, theta 5e5, factor 16 over an
    original context of 8192, betas 32 / 1. dim(n) = 128 ln(8192 / 2 pi n)
    / (2 ln 5e5): dim(32) = 128 x 3.70730 / 26.24473 = 18.08 -> low 18,
    dim(1) = 128 x 7.17304 / 26.24473 = 34.98 -> high 35. So dimensions
    0..18 keep theta^(-2i/128), 35..63 have it over 16, and i = 18 + j
    blends with ramp j / 17."""
    rope = RopeConfig(base=500000.0, factor=16.0, original_max_position=8192,
                      beta_fast=32.0, beta_slow=1.0,
                      attention_factor=1.2772588722239782)
    got = np.asarray(transformer.yarn_inv_freq(128, rope), np.float64)
    plain = lambda i: 500000.0 ** (-2 * i / 128)  # noqa: E731
    assert got.shape == (64,)
    np.testing.assert_allclose(got[0], 1.0, rtol=1e-6)
    np.testing.assert_allclose(got[18], 0.0249550, rtol=1e-4)  # exp(-3.69067)
    np.testing.assert_allclose(got[35], 0.000764497 / 16, rtol=1e-4)
    np.testing.assert_allclose(got[63], plain(63) / 16, rtol=1e-5)
    for i, ramp in ((19, 1 / 17), (26, 8 / 17), (34, 16 / 17)):
        np.testing.assert_allclose(
            got[i], (1 - ramp) * plain(i) + ramp * plain(i) / 16, rtol=1e-5)
    assert rope.scale == pytest.approx(0.1 * math.log(16) + 1, abs=1e-12)
    assert RopeConfig(base=5e5, factor=16.0).scale == pytest.approx(
        1.2772588722239782)
    # cos and sin carry the factor; a plain table does not
    pos = jnp.arange(5)[None]
    cos, sin = transformer.rope_tables(pos, 128, rope)
    np.testing.assert_allclose(cos[0, 0], rope.scale, rtol=1e-6)
    np.testing.assert_allclose(sin[0, 1, 0], rope.scale * math.sin(1.0),
                               rtol=1e-6)
    cos0, _ = transformer.rope_tables(pos, 128, 500000.0)
    np.testing.assert_allclose(cos0[0, 0], 1.0, rtol=1e-6)
    # and the reference computes the same frequencies, written apart
    np.testing.assert_allclose(ref.inv_freq(YARN, 128), got, rtol=1e-6)


# ---- (e) the scan over periods ----

def test_scan_over_periods_equals_a_loop_over_layers():
    cfg, params = model(HF_KEYS)
    tok = tokens(2)
    T = tok.shape[0]
    pos = jnp.arange(T, dtype=jnp.int32)[None]
    seg = jnp.ones((1, T), jnp.int32)
    h0 = params["embedding"][tok][None]
    ropes = transformer.rope_tables_by_kind(cfg, pos)
    cos = {k: v[0] for k, v in ropes.items()}
    sin = {k: v[1] for k, v in ropes.items()}
    # one program a KIND of block, run a layer at a time
    block = jax.jit(transformer._block, static_argnums=(0, 10),
                    static_argnames="kind")
    for remat in (False, "full"):
        got, aux = jax.jit(functools.partial(
            transformer.apply_layer_stack, cfg, attn_impl="reference",
            remat=remat))(h0, params["layers"], cos, sin, seg, pos)
        h, loads = h0, []
        for i, kind in enumerate(cfg.layer_kinds):
            lp = jax.tree.map(lambda a: a[i], params["layers"])
            h, _, a = block(
                cfg, h, lp, cos, sin, seg, pos, None, None, None,
                "reference", kind=kind)
            loads.append(a["expert_load"])
        # activations of order 30 after 8 layers, float32 either way
        np.testing.assert_allclose(got, h, atol=2e-3, rtol=1e-4)
        # per-layer outputs come back stacked by LAYER, in order
        assert aux["expert_load"].shape == (8, 8)
        np.testing.assert_allclose(aux["expert_load"], jnp.stack(loads),
                                   atol=1e-6)


def test_a_pattern_that_is_no_whole_number_of_periods_is_one_period():
    cfg, _ = model({**HF_KEYS, "num_hidden_layers": 6,
                    "layer_types": HF_KEYS["layer_types"][:6]})
    assert len(cfg.period_kinds) == 6


# A period-1 model traces the program it traced before the layer pattern:
# sha256 of the lowered train_grad_sliced text of one Qwen-like and one
# OLMoE-like grid, taken on the parent commit (f176dba) by this file's own
# ``lowered_grad_text`` run against that checkout.
PARENT_TEXT = {
    "qwen": "7a5e8784b462f38be131fdc520e47b30e231745693b614898a64891e08f06e1e",
    "olmoe": "ea4a23430165ecb88be0ebce87b99de108998b02a8a1104356179fd604486821",
}
# ... and the programs beside the Mamba-2 scan's kernel (PR 50), which
# must not see it: the OLMoE-like grid under the four-chip cell's ``e4``
# mesh (four of the host platform's devices) and a phi4flash-like grid
# whose scans are S6, taken the same way on 47ab89e.
PARENT_TEXT.update({
    "olmoe-e4":
        "6d1e87740353d4124c23828ce8cdb1123f9a01cd313b7f68de0ca31778d5f64d",
    "phi-s6":
        "19c49f246c20808df28b1b1fca7e894ba5136ae40e536dbf681d999432d1d8e2",
})


def lowered_grad_text(which: str) -> str:
    from areal_tpu.api.data import MicroBatchSpec, SequenceSample
    from areal_tpu.api.model import FinetuneSpec
    from areal_tpu.backend.jax_train import JaxTrainEngine, OptimizerConfig
    from areal_tpu.models.config import tiny_config

    kw = dict(vocab_size=64, n_layers=2, hidden_dim=32, n_q_heads=4,
              n_kv_heads=2)
    mesh = None
    if which == "qwen":
        cfg = tiny_config(**kw, use_attention_bias=True,
                          tie_word_embeddings=True)
    elif which == "phi-s6":  # Mamba-1, window, Mamba-1 -> memory, full
        cfg = hf.config_from_hf(types.SimpleNamespace(
            model_type="phi4flash", num_hidden_layers=4,
            layer_pattern="MSMF", hidden_size=32, num_attention_heads=4,
            num_key_value_heads=2, intermediate_size=48, vocab_size=64,
            sliding_window=8, mb_per_layer=2, layer_norm_eps=1e-5,
            tie_word_embeddings=True, max_position_embeddings=4096))
    else:
        cfg = tiny_config(**kw, use_qk_norm=True, qk_norm_extent="proj",
                          moe=dict(num_experts=4, top_k=2,
                                   capacity_factor=None,
                                   norm_topk_prob=False))
        if which == "olmoe-e4":  # the four-chip cell's mesh, on the host
            from areal_tpu.parallel import mesh as pmesh

            mesh = pmesh.make_mesh(pmesh.ParallelSpec.parse("e4"))
    eng = JaxTrainEngine(
        cfg, transformer.init_params(cfg, jax.random.PRNGKey(0)),
        OptimizerConfig(type="sgd", lr=1e-2), FinetuneSpec(1, 8, 4),
        mesh=mesh, compute_dtype="float32", length_bucket=16,
        rows_bucket=4 if mesh is not None else 2, seqs_bucket=4, remat=True)
    rng = np.random.RandomState(3)
    lens = rng.randint(6, 14, 6)
    total = int(lens.sum())
    sample = SequenceSample.from_default(
        ids=[f"s{i}" for i in range(6)],
        data={"packed_input_ids": rng.randint(2, 64, total).astype(np.int32),
              "loss_mask": np.ones(total, np.float32)},
        seqlens=lens.tolist())

    def sq_loss(logits, batch):
        w = (batch["segment_ids"] > 0).astype(jnp.float32)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return jnp.sum(jnp.sum(lp * lp, axis=-1) * w), {"n": jnp.sum(w)}

    texts = []
    real = eng._get_sliced_grad_fn

    def get_fn(loss_fn, with_carry, R, remat=False):
        fn = real(loss_fn, with_carry, R, remat)

        def call(*args):
            if not texts:
                texts.append(fn.lower(*args).as_text())
            return fn(*args)

        return call

    eng._get_sliced_grad_fn = get_fn
    eng.train_batch(sample, MicroBatchSpec(max_tokens_per_mb=64), sq_loss,
                    lambda mb: mb.n_tokens)
    return texts[0]


@pytest.mark.parametrize("which", sorted(PARENT_TEXT))
def test_a_period_one_model_lowers_to_the_parents_program(which):
    text = lowered_grad_text(which)
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_TEXT[which]


# ---- (f) decode through the cache ----

@pytest.mark.parametrize("which", sorted(KEYS))
def test_decode_through_the_cache_matches_the_packed_forward(which):
    """A prompt longer than the window, then greedy decode through the KV
    cache: every step's logits against the packed forward (and so the
    reference) over the sequence so far — the cache keeps every slot, a
    sliding layer reads the last 8 of them, each kind turns its own RoPE
    table."""
    keys = ONE_PERIOD[which]
    cfg, params = model(keys)
    P, N = 13, 4
    seq = [int(t) for t in np.asarray(tokens(5, P))]
    state = gen.prefill_state(params, cfg, jnp.asarray([seq], jnp.int32),
                              jnp.asarray([P], jnp.int32), P + N + 1,
                              attn_impl="reference")
    logits = state["last_logits"][0]
    kv = {"k": state["kv_k"], "v": state["kv_v"]}
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
    """``generate_batch()`` itself: greedy tokens and their logprobs for a
    prompt longer than the window equal the packed forward's."""
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
