"""The layers a hybrid model (``model_type`` nemotron_h) is made of, one at
a time, against ``benchmark/reference_nemotron_h.py`` on the CPU at the
tiny size of tests/test_nemotron_h_parity.py: the chunked scan against the
sequential one (lengths that are no multiple of the chunk, documents that
end anywhere), the shares of a layer against the uncut layer, the scan
over periods against a loop, the published weight names, and the named
refusals of the code that cannot run such a layer.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_nemotron_h_parity import HF_KEYS, PERIOD, SHARE_KEYS, TOL, model
from test_nemotron_h_parity import system_logits, tokens

from areal_tpu.models import generate as gen
from areal_tpu.models import hf, moe as moemod, ssm as ssmmod, transformer
from areal_tpu.models.config import ATTENTION_ONLY, MAMBA, MOE_ONLY, SSMConfig
from areal_tpu.parallel import pipeline, ring
from benchmark import reference_nemotron_h as ref


def scan_inputs(T, seed=0, B=2, H=4, P=8, G=2, N=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (B, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)) - 2.0)
    A = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=2.7))
    Bm = jax.random.normal(ks[3], (B, T, G, N))
    Cm = jax.random.normal(ks[4], (B, T, G, N))
    return x, dt, A, Bm, Cm


@pytest.mark.parametrize("T", [5, 8, 13, 37, 64])
def test_the_chunked_scan_equals_the_sequential_one(T):
    """…at lengths that are no multiple of the chunk (8), with documents
    that end inside chunks, on chunk borders, and span several chunks."""
    x, dt, A, Bm, Cm = scan_inputs(T)
    cuts = sorted({0, T // 3, min(T, 8), (2 * T) // 3, T})
    seg = np.zeros((2, T), np.int32)
    for i, (a, b) in enumerate(zip(cuts, cuts[1:])):
        seg[0, a:b] = i + 1
    seg[1, :max(T - 3, 1)] = 1  # one document, then padding
    got = ssmmod.ssd_scan(x, dt, A, Bm, Cm, jnp.asarray(seg), chunk=8)
    H, G = x.shape[2], Bm.shape[2]
    of_head = np.arange(H) // (H // G)
    for b in range(2):
        for s in np.unique(seg[b]):
            idx = np.nonzero(seg[b] == s)[0]
            a, e = idx[0], idx[-1] + 1
            want = ref.scan(x[b, a:e], dt[b, a:e], A,
                            Bm[b, a:e][:, of_head], Cm[b, a:e][:, of_head])
            if s:  # padding's output is never read
                np.testing.assert_allclose(got[b, a:e], want,
                                           atol=1e-4, rtol=1e-4)
    assert ssmmod.geometry_counts()[(2, T, 8, 4, 2)] >= 1


def test_the_scan_differentiates_to_the_sequential_ones_gradients():
    x, dt, A, Bm, Cm = scan_inputs(21, seed=3, B=1)
    seg = jnp.asarray([[1] * 9 + [2] * 12])
    of_head = np.arange(4) // 2

    def chunked(x, dt, A, Bm, Cm):
        return jnp.sum(jnp.sin(ssmmod.ssd_scan(x, dt, A, Bm, Cm, seg, 8)))

    def sequential(x, dt, A, Bm, Cm):
        return sum(jnp.sum(jnp.sin(ref.scan(
            x[0, a:e], dt[0, a:e], A, Bm[0, a:e][:, of_head],
            Cm[0, a:e][:, of_head]))) for a, e in ((0, 9), (9, 21)))

    got = jax.grad(chunked, argnums=(0, 1, 2, 3, 4))(x, dt, A, Bm, Cm)
    want = jax.grad(sequential, argnums=(0, 1, 2, 3, 4))(x, dt, A, Bm, Cm)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-4 * float(
            jnp.max(jnp.abs(w))), rtol=2e-3)


# ---- (c) the shares of a layer add up to the uncut layer ----

def one_layer(kind, seed=0):
    """(HF keys of the uncut layer's model, its parameters, u [T, D])."""
    keys = {**HF_KEYS, "mamba_num_heads": 16, "n_groups": 8,
            "num_attention_heads": 8, "num_key_value_heads": 2,
            "n_routed_experts": 16, "hybrid_override_pattern": PERIOD,
            "num_hidden_layers": 11}
    _, params = model(keys, seed)
    lp = {k: v[0] for k, v in params["layers"][kind].items()}
    u = jax.random.normal(jax.random.PRNGKey(seed + 7), (19, 64))
    return keys, lp, u


def test_the_eight_head_shares_of_a_mamba_layer_add_up():
    keys, lp, u = one_layer(MAMBA)
    H, P, G, N = 16, 8, 8, 16
    di, n = H * P, 8
    total = 0.0
    for s in range(n):
        heads = np.arange(s * H // n, (s + 1) * H // n)
        ch = (heads[:, None] * P + np.arange(P)).ravel()  # channels of x, z
        gr = np.arange(s * N, (s + 1) * N)  # the one group's B or C
        cols = np.concatenate([ch, di + ch, 2 * di + gr,
                               2 * di + G * N + gr,
                               2 * di + 2 * G * N + heads])
        conv = np.concatenate([ch, di + gr, di + G * N + gr])
        share = {
            "in_proj": lp["in_proj"][:, cols], "conv_w": lp["conv_w"][:, conv],
            "conv_b": lp["conv_b"][conv], "dt_bias": lp["dt_bias"][heads],
            "A_log": lp["A_log"][heads], "D": lp["D"][heads],
            "norm": lp["norm"][ch], "out_proj": lp["out_proj"][ch],
        }
        total = total + ssmmod.mamba_mixer(
            u[None], share, SSMConfig(n_heads=H // n, head_dim=P, n_groups=1,
                                      state_dim=N, chunk_size=8),
            1e-5, None)[0]
    np.testing.assert_allclose(total, ref.mamba(u, keys, lp), **TOL)


def test_the_eight_head_shares_of_an_attention_layer_add_up():
    keys, lp, u = one_layer(ATTENTION_ONLY)
    nq, nkv, dh, n = 8, 2, 16, 8
    share_keys = {**keys, "num_attention_heads": 1, "num_key_value_heads": 1}
    cfg = hf.config_from_hf(types.SimpleNamespace(**share_keys))
    seg, pos = jnp.ones((1, 19), jnp.int32), jnp.arange(19)[None]
    total = 0.0
    for s in range(n):  # q head s with its kv head, which 4 shares hold
        q = np.arange(s * dh, (s + 1) * dh)
        kv = np.arange((s * nkv // nq) * dh, (s * nkv // nq + 1) * dh)
        share = {"ln": lp["ln"], "wq": lp["wq"][:, q], "wk": lp["wk"][:, kv],
                 "wv": lp["wv"][:, kv], "wo": lp["wo"][q]}
        total = total + (transformer._mixer_block(
            cfg, ATTENTION_ONLY, u[None], share, seg, pos, "reference",
            False, None)[0] - u[None])[0]
    normed = ref._rms(u, lp["ln"], 1e-5)
    np.testing.assert_allclose(total, ref.attention(normed, keys, lp), **TOL)


@pytest.mark.parametrize("shares", [4, 8])
def test_the_expert_shares_and_the_shared_expert_once_add_up(shares):
    keys, lp, u = one_layer(MOE_ONLY)
    held = 16 // shares
    total = 0.0
    for s in range(shares):
        share_keys = {**keys, "n_routed_experts": held,
                      "num_routed_experts": 16, "expert_shard_count": shares,
                      "expert_shard_index": s}
        cfg = hf.config_from_hf(types.SimpleNamespace(**share_keys))
        share = {**lp, "e_up": lp["e_up"][s * held:(s + 1) * held],
                 "e_down": lp["e_down"][s * held:(s + 1) * held]}
        y, aux = moemod.moe_mlp(u[None], share, cfg.moe)
        assert float(aux["dropped_frac"]) == 0.0
        total = total + y[0]
    # every share computed the shared expert: count it once
    total = total - (shares - 1) * ref.shared(u, keys, lp)
    np.testing.assert_allclose(total, ref.moe(u, keys, lp), **TOL)


# ---- (d) the scan over periods, weights, counters, refusals ----

def test_scan_over_periods_equals_a_loop_over_layers():
    cfg, params = model(HF_KEYS)
    tok = tokens(4, 24)
    T = tok.shape[0]
    seg, pos = jnp.ones((1, T), jnp.int32), jnp.arange(T)[None]
    h = params["embedding"][tok][None]
    seen = {}
    for kind in cfg.layer_kinds:
        i = seen.get(kind, 0)
        seen[kind] = i + 1
        lp = {k: v[i] for k, v in params["layers"][kind].items()}
        h = transformer._mixer_block(cfg, kind, h, lp, seg, pos, "reference",
                                     False, None)[0]
    h = transformer.rms_norm(h, params["final_ln"], cfg.rms_norm_eps)
    np.testing.assert_allclose((h @ params["lm_head"])[0],
                               system_logits(params, cfg, tok), **TOL)


def test_weights_round_trip_through_the_published_names():
    cfg, params = model(SHARE_KEYS)
    sd = hf.params_to_hf_state_dict(params, cfg)
    assert sd["backbone.layers.1.mixer.conv1d.weight"].shape == (
        cfg.ssm.conv_dim, 1, 4)
    for name in ("backbone.layers.0.mixer.gate.e_score_correction_bias",
                 "backbone.layers.0.mixer.experts.1.up_proj.weight",
                 "backbone.layers.0.mixer.shared_experts.down_proj.weight",
                 "backbone.layers.0.mixer.fc1_latent_proj.weight",
                 "backbone.layers.1.mixer.in_proj.weight",
                 "backbone.layers.10.mixer.q_proj.weight",
                 "backbone.layers.21.norm.weight", "backbone.norm_f.weight"):
        assert name in sd, name
    back = hf.flatten_pytree(hf.params_from_hf_state_dict(sd, cfg))
    for name, x in hf.flatten_pytree(params).items():
        np.testing.assert_array_equal(back[name], np.asarray(x), err_msg=name)


REFUSALS = {
    "generate": lambda cfg, params: gen.generate_batch(
        params, cfg, jnp.ones((2, 8), jnp.int32), jnp.full((2,), 8),
        jax.random.PRNGKey(0),
        gen.GenerationHyperparameters(max_new_tokens=4), 4, 1, 0,
        attn_impl="reference"),
    "prefill": lambda cfg, params: gen.prefill_state(
        params, cfg, jnp.ones((2, 8), jnp.int32), jnp.full((2,), 8), 16,
        attn_impl="reference"),
    "kv_cache": lambda cfg, params: transformer.init_kv_cache(cfg, 2, 16),
}


@pytest.mark.parametrize("entry", sorted(REFUSALS))
def test_decoding_is_refused_by_name_not_by_a_shape_error(entry):
    cfg, params = model(HF_KEYS)
    assert gen.decode_refusal(cfg).startswith("recurrent_decode_state")
    dense = hf.config_from_hf(types.SimpleNamespace(
        model_type="llama", num_hidden_layers=2, hidden_size=32,
        num_attention_heads=4, intermediate_size=64, vocab_size=64))
    assert gen.decode_refusal(dense) is None
    with pytest.raises(NotImplementedError, match="recurrent_decode_state"):
        REFUSALS[entry](cfg, params)


def test_the_ring_and_the_pipeline_refuse_by_name():
    cfg, _ = model(HF_KEYS)
    mesh = types.SimpleNamespace(shape={"dp": 1, "fsdp": 1, "ep": 1, "sp": 2,
                                        "tp": 1, "pp": 2})
    assert ring.ring_refusal(cfg) == "state_space_scan"
    assert ring.ring_refusal(cfg, ATTENTION_ONLY) == "state_space_scan"
    assert "state_space_scan" in ring.RING_REFUSALS
    assert not ring.ring_eligible(mesh, cfg, 2, 64)
    mellum = hf.config_from_hf(types.SimpleNamespace(
        model_type="mistral", num_hidden_layers=2, hidden_size=32,
        num_attention_heads=4, intermediate_size=64, vocab_size=64,
        sliding_window=8))
    assert ring.ring_refusal(mellum) == "sliding_window"
    assert pipeline.pick_pp_microbatches(mesh, cfg, 4, seq_len=64) is None
    assert "mixer_layers" in pipeline._FALLBACK_HINTS
    assert "mixer_layers" in pipeline._WARNED_FALLBACKS


def test_a_period_is_cut_into_runs_that_are_scanned():
    E, M, A = MOE_ONLY, MAMBA, ATTENTION_ONLY
    runs = transformer.period_runs
    assert runs((E, M) * 5 + (A,)) == (((E, M), 5), ((A,), 1))
    assert runs((M, M, A)) == (((M,), 2), ((A,), 1))
    assert runs((E, E, M, E)) == (((E,), 2), ((M,), 1), ((E,), 1))
    assert runs((M, E, M, E, M, A)) == (((M, E), 2), ((M,), 1), ((A,), 1))
    for kinds in ((E, M) * 5 + (A,), (M, E, M, A, E), (A,)):
        assert sum((unit * n for unit, n in runs(kinds)), ()) == kinds
    # the program of a period holds ONE expert layer and ONE scan, not five
    cfg, params = model({**HF_KEYS, "num_hidden_layers": 11})
    tok = tokens(5, 16)
    text = str(jax.make_jaxpr(lambda p: system_logits(p, cfg, tok))(params))
    assert text.count("cumsum") < 2 * 5  # one Mamba layer's two, not five's


@pytest.mark.parametrize("n_tokens", [5, 16, 21])
def test_the_latent_source_is_whole_row_tiles_and_nothing_reads_the_pad(
        n_tokens, monkeypatch):
    """The expert pass gathers from the latent tokens padded to whole row
    tiles (``moe._whole_row_tiles``; a tile of 8 here, so that 5 and 21
    tokens are padded and 16 are not): outputs, and the gradients of the
    tokens and of every weight, are those of the unpadded source."""
    cfg, params = model(SHARE_KEYS)
    lp = jax.tree.map(lambda x: x[0], params["layers"][MOE_ONLY])
    lp = {k: v for k, v in lp.items() if k != "ln"}
    x = jax.random.normal(jax.random.PRNGKey(3), (1, n_tokens, 64))

    def loss(lp, x):
        y, aux = moemod.moe_mlp(x, lp, cfg.moe)
        return jnp.sum(y ** 2), aux["local_rows"]

    monkeypatch.setattr(moemod, "_whole_row_tiles", lambda xe: xe)
    (want, rows), want_g = jax.value_and_grad(loss, argnums=(0, 1),
                                              has_aux=True)(lp, x)
    monkeypatch.undo()
    monkeypatch.setattr(moemod, "_ROW_TILE", 8)
    padded = moemod._whole_row_tiles(jnp.ones((n_tokens, 32)))
    assert padded.shape == (-(-n_tokens // 8) * 8, 32)
    (got, _), got_g = jax.value_and_grad(loss, argnums=(0, 1),
                                         has_aux=True)(lp, x)
    assert rows > 0
    np.testing.assert_allclose(got, want, rtol=1e-6)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        a, b, rtol=1e-5, atol=1e-5 * float(jnp.abs(b).max())), got_g, want_g)


@pytest.mark.parametrize("spec", ["d2f2t2", "f4"])
def test_a_mixer_model_on_a_mesh_matches_one_device(spec):
    """``sharding.param_partition_specs`` of a model whose layers are one
    mixer each (a spec tree per kind: ZeRO over ``fsdp``, attention's heads
    and the shared expert's width over ``tp``) places every leaf, and the
    forward on the mesh is the single device's."""
    from areal_tpu.parallel import mesh as pmesh
    from areal_tpu.parallel import sharding as psh

    cfg, params = model({**SHARE_KEYS, "vocab_size": 96})
    rng = np.random.default_rng(0)
    B, T = 4, 16
    tok = rng.integers(2, 96, (B, T)).astype(np.int32)
    pos = np.tile(np.arange(T, dtype=np.int32), (B, 1))
    seg = np.ones((B, T), np.int32)
    kw = dict(segment_ids=seg, attn_impl="reference", return_kv=False)
    want, _ = transformer.forward(params, cfg, tok, pos, **kw)
    m = pmesh.make_mesh(pmesh.ParallelSpec.parse(spec))
    sharded = psh.shard_params(params, m, cfg)
    placed = psh.named_shardings(m, psh.param_partition_specs(cfg))
    jax.tree.map(lambda x, s: x.sharding == s or pytest.fail(), sharded,
                 placed)

    def fwd(p):
        with psh.activation_sharding(m):
            return transformer.forward(p, cfg, tok, pos, **kw)[0]

    np.testing.assert_allclose(jax.jit(fwd)(sharded), want, atol=2e-4)


def test_the_scopes_the_benchmark_reads_are_the_programs():
    from areal_tpu.base import telemetry
    from benchmark import ssm_trace

    assert telemetry.SSM_SCOPES == ssm_trace.SSM_SCOPES
    assert telemetry.LATENT_MOE_SCOPES == ssm_trace.LATENT_SCOPES
    new = set(telemetry.SSM_SCOPES + telemetry.LATENT_MOE_SCOPES)
    assert not new & set(telemetry.DEVICE_SCOPES + telemetry.MOE_SCOPES)
    cfg, params = model({**HF_KEYS, "num_hidden_layers": 11})
    tok = tokens(6, 16)
    text = jax.jit(lambda p: system_logits(p, cfg, tok)).lower(
        params).as_text(debug_info=True)
    for scope in new | {"moe_router", "moe_dispatch", "moe_experts"}:
        assert scope in text, scope


def test_a_mamba_layers_kernels_sit_under_the_scans_scope():
    """A Mamba layer at the widths the scan's kernel takes (2 heads of 64,
    one group of 128 states, chunks of 128), lowered for a TPU: the
    forward and the backward kernel are filed under ``ssm_scan`` by the
    benchmark's own reduction (``ssm_scan_busy_pct`` / ``ssm_scan_
    roofline``); interpreted on the CPU the layer answers as the XLA form
    does, under the same five-field geometry key."""
    import re

    from areal_tpu.ops.pallas import ssd_scan
    from benchmark import ssm_trace

    H, P, G, N, Q = 2, 64, 1, 128, 128
    cfg = SSMConfig(n_heads=H, head_dim=P, n_groups=G, state_dim=N,
                    chunk_size=Q)
    lp = {k: v[0] for k, v in ssmmod.init_mamba_params(
        cfg, 1, 32, jax.random.PRNGKey(0), jnp.float32).items()}
    u = jax.random.normal(jax.random.PRNGKey(1), (1, 200, 32))
    seg = jnp.asarray([[1] * 90 + [2] * 100 + [0] * 10])

    def grad(impl):
        return jax.jit(jax.value_and_grad(lambda lp, u: jnp.sum(
            ssmmod.mamba_mixer(u, lp, cfg, 1e-5, seg, impl) ** 2),
            argnums=(0, 1)))

    text = grad("pallas").trace(lp, u).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    names = re.findall(r'loc\("([^"]*pallas_call)"', text)
    for kernel_name in (ssd_scan.FWD_NAME, ssd_scan.BWD_NAME):
        mine = [n for n in names if f"/{kernel_name}/" in n]
        assert mine and {ssm_trace.scope_of(n) for n in mine} == {
            "ssm_scan"}, (kernel_name, names)
    key = (1, 200, Q, H, G)
    before = ssmmod.geometry_counts().get(key, 0)
    (got, dgot), (want, dwant) = (grad(i)(lp, u) for i in (
        "pallas_interpret", "reference"))
    assert ssmmod.geometry_counts()[key] == before + 2
    np.testing.assert_allclose(got, want, rtol=1e-4)
    for a, b in zip(jax.tree.leaves(dgot), jax.tree.leaves(dwant)):
        np.testing.assert_allclose(
            a, b, atol=2e-4 * float(jnp.max(jnp.abs(b))) + 1e-7, rtol=2e-3)
