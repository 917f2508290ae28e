"""Phi-4-mini-flash (``model_type`` phi4flash; SambaY) through the system
against the benchmark's plain reference (``benchmark/reference_sambay.py``:
float32, the recurrence a token at a time, attention as explicit softmaxes
a head, one document at a time) on seeded weights, on the CPU at a tiny
size: hidden 64, 8 query / 4 key-value heads of 8 (4 / 2 pairs, values of
16), a selective scan over 128 channels of 16 states, window 16, the
pattern ``(M,S)x2 . (M,F) . (G,X)x2`` — two periods in each scanned run.

Both sides compute in float32 here, so they differ by the order of
float32 sums only. The lambda term or the sub-norm dropped, the cross
layers on the wrong layer's K/V, the memory taken behind the gate, Delta
without its bias or the window off move logits by 1e-2 and more.
"""

import functools
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import hf, ssm, transformer
from areal_tpu.models.config import (
    CROSS, FULL, GMU, MEMORY, S6, SHARED_KV, SLIDING, S6Config)
from areal_tpu.ops import attention
from benchmark import reference_sambay as ref

PATTERN = "MSMSMFGXGX"
HF_KEYS = {
    "model_type": "phi4flash", "num_hidden_layers": 10,
    "layer_pattern": PATTERN, "first_layer_index": 3, "hidden_size": 64,
    "num_attention_heads": 8, "num_key_value_heads": 4,
    "intermediate_size": 96, "vocab_size": 97, "sliding_window": 16,
    "mb_per_layer": 2, "layer_norm_eps": 1e-5, "tie_word_embeddings": True,
    "max_position_embeddings": 4096,
}
TOL = dict(atol=3e-4, rtol=3e-4)
POS = jnp.arange(64)[None]  # a row's positions (64 tokens)
NORMS = ("ln1", "ln2", "final_ln", "subln")
AS_DRAWN = ("conv_w", "dt_bias", "A_log", "dt_proj")
JITTERED = ("conv_b", "D", "ln1_b", "ln2_b", "final_ln_b", "bq", "bk", "bv",
            "bo")
LAMBDAS = ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")


def model(keys=HF_KEYS, seed=0, scale=0.3):
    """(config, float32 params): init_params with the matrices scaled up
    (so that every mixer matters), the norm weights random around 1,
    every bias and the skip ``D`` random, lambda's vectors large enough
    for lambda to leave lambda_init, the decay's parameters as drawn.
    Built once a set of keys: no test writes into the tree it gets."""
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
        elif leaf in LAMBDAS:
            flat[name] = 0.3 * jax.random.normal(k, x.shape)
        elif leaf not in AS_DRAWN:
            flat[name] = x * (scale / 0.02)
    return cfg, hf.unflatten_pytree(flat)


def tokens(seed=0, T=43):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(2, HF_KEYS["vocab_size"], T), jnp.int32)


def system_logits(params, cfg, tok, seg=None, remat=False, impl="reference"):
    """Logits of a packed grid ``tok`` [B, T] (or one document [T])."""
    one = tok.ndim == 1
    if one:
        tok = tok[None]
    B, T = tok.shape
    seg = jnp.ones((B, T), jnp.int32) if seg is None else seg
    out, _ = transformer.forward(
        params, cfg, tok, jnp.broadcast_to(jnp.arange(T), (B, T)),
        segment_ids=seg,
        attn_impl=impl, return_kv=False, remat=remat)
    return out[0] if one else out


def mean_logprob(logits, tok):
    lp = jax.nn.log_softmax(logits[:-1], -1)
    return jnp.mean(jnp.take_along_axis(lp, tok[1:, None], -1))


# ---- (a) the family ----

def test_the_family_reads_the_pattern_and_the_sources():
    cfg, params = model()
    assert cfg.layer_kinds == tuple(
        {"M": S6, "S": SLIDING, "F": FULL, "G": GMU, "X": CROSS}[c]
        for c in PATTERN)
    assert cfg.is_hybrid and cfg.pos_embedding == "none"
    assert not cfg.has_mixer_layers and cfg.has_cacheless_layers
    assert cfg.norm_type == "layer" and cfg.differential_attention
    assert cfg.s6 == S6Config(d_inner=128, state_dim=16, conv_kernel=4,
                              dt_rank=4)
    # derived from the pattern, no knob: the last M before the first G,
    # the F before the first X
    assert (cfg.memory_source, cfg.kv_source) == (4, 5)
    assert cfg.cross_layer_reads == {MEMORY: 2, SHARED_KV: 2}
    assert cfg.block_counts() == {
        "s6/dense": 3, "sliding/dense": 2, "full/dense": 1, "gmu/dense": 2,
        "cross/dense": 2}
    assert {k: v["ln1"].shape[0] for k, v in params["layers"].items()} == {
        S6: 3, SLIDING: 2, FULL: 1, GMU: 2, CROSS: 2}
    assert "wk" not in params["layers"][CROSS]
    assert cfg.first_layer_index == 3  # lambda_init reads 3 + the layer
    again = hf.config_from_hf(types.SimpleNamespace(**hf.hf_config_dict(cfg)))
    assert again == cfg


def test_the_published_pattern_is_three_runs_around_two_sources():
    pattern = hf.sambay_pattern(32, 2)
    assert pattern == "MS" * 8 + "MF" + "GX" * 7
    assert pattern == ref.pattern_of(
        {"num_hidden_layers": 32, "mb_per_layer": 2})
    assert [pattern.count(c) for c in "MSFGX"] == [9, 8, 1, 7, 7]
    keys = {k: v for k, v in HF_KEYS.items()
            if k not in ("layer_pattern", "first_layer_index")}
    cfg = hf.config_from_hf(types.SimpleNamespace(
        **{**keys, "num_hidden_layers": 32}))
    assert (cfg.memory_source, cfg.kv_source) == (16, 17)
    assert cfg.cross_layer_reads == {MEMORY: 7, SHARED_KV: 7}
    assert (ref.memory_source(pattern), ref.kv_source(pattern)) == (16, 17)


def test_parameter_count_at_the_published_widths():
    """The issue's arithmetic: M 41.24 M, S / F 19.67 M, G 26.21 M, X
    13.11 M a mixer, the MLP 78.64 M, the whole model 3.85 B."""
    keys = {"model_type": "phi4flash", "num_hidden_layers": 32,
            "hidden_size": 2560, "num_attention_heads": 40,
            "num_key_value_heads": 20, "intermediate_size": 10240,
            "vocab_size": 200064, "sliding_window": 512, "mb_per_layer": 2,
            "tie_word_embeddings": True}
    cfg = hf.config_from_hf(types.SimpleNamespace(**keys))
    mlp = 3 * 2560 * 10240 + 2 * 2560
    count = transformer._block_param_count
    assert round((count(cfg, True, S6) - mlp) / 1e4) == 4124
    assert round((count(cfg, True, FULL) - mlp) / 1e4) == 1966
    assert round((count(cfg, True, GMU) - mlp) / 1e4) == 2621
    assert round((count(cfg, True, CROSS) - mlp) / 1e4) == 1311
    assert round(transformer.param_count(cfg) / 1e7) == 385


# ---- (b) the program against the reference ----

def test_logits_match_the_reference():
    cfg, params = model()
    tok = tokens()  # 43 tokens: further than the window, no multiple of 64
    np.testing.assert_allclose(system_logits(params, cfg, tok),
                               ref.logits(params, HF_KEYS, tok), **TOL)


def test_loss_and_gradients_match_the_reference():
    cfg, params = model()
    tok = tokens(1)
    got_l, got_g = jax.jit(jax.value_and_grad(
        lambda p: mean_logprob(system_logits(p, cfg, tok, remat="full"),
                               tok)))(params)
    want_l, want_g = jax.jit(jax.value_and_grad(
        lambda p: -ref.loss(p, HF_KEYS, tok)))(params)
    np.testing.assert_allclose(got_l, want_l, atol=1e-5, rtol=1e-5)
    got, want = hf.flatten_pytree(got_g), hf.flatten_pytree(want_g)
    assert sorted(got) == sorted(want)
    for name in sorted(got):
        scale = float(jnp.abs(want[name]).max())
        # every leaf matters, the sources' too — but k's bias: a softmax
        # does not see a constant added to every key's score
        assert scale > 1e-6 or name.endswith("/bk"), name
        np.testing.assert_allclose(got[name], want[name],
                                   atol=2e-3 * scale + 1e-7, rtol=2e-3,
                                   err_msg=name)


WRONG = {
    "lambda_term_dropped": ("combine", lambda real: lambda o1, o2, lam: o1),
    "sub_norm_dropped": ("sub_norm", lambda real: lambda o, w, eps: o),
    "cross_reads_the_window_layers_kv": (
        "kv_source", lambda real: lambda pattern: pattern.rindex("S")),
    "memory_behind_the_gate": (
        "memory_of", lambda real: lambda y, z: y * jax.nn.silu(z)),
    "lambda_init_of_layer_0": (
        "lambda_init_of", lambda real: lambda cfg, layer: real(
            {**cfg, "first_layer_index": 0}, layer)),
}


@pytest.mark.parametrize("which", sorted(WRONG) + [
    "window_off", "delta_without_its_bias"])
def test_a_wrong_reference_is_told_apart(which, monkeypatch):
    cfg, params = model()
    tok = tokens()
    keys, wrong = HF_KEYS, params
    if which in WRONG:
        name, make = WRONG[which]
        monkeypatch.setattr(ref, name, make(getattr(ref, name)))
    elif which == "window_off":
        keys = {**HF_KEYS, "sliding_window": None}
    else:
        s6 = dict(params["layers"][S6])
        s6["dt_bias"] = jnp.zeros_like(s6["dt_bias"])
        wrong = {**params, "layers": {**params["layers"], S6: s6}}
    got = system_logits(params, cfg, tok)
    assert float(jnp.abs(got - ref.logits(wrong, keys, tok)).max()) > 1e-2


# ---- (c) packed rows ----

ROW_PATTERNS = {"scan_and_conv": "MS", "window": "MSMS", "full": "MF",
                "cross_and_memory": "MFGX", "whole": PATTERN}


@pytest.mark.parametrize("which", sorted(ROW_PATTERNS))
def test_a_document_behind_another_equals_the_document_alone(which):
    pattern = ROW_PATTERNS[which]
    cfg, params = model({**HF_KEYS, "layer_pattern": pattern,
                         "num_hidden_layers": len(pattern)})
    a, b = tokens(2, 21), tokens(3, 37)
    T = 64
    row = jnp.concatenate([a, b, jnp.zeros(T - 58, jnp.int32)])[None]
    seg = jnp.asarray([[1] * 21 + [2] * 37 + [0] * (T - 58)], jnp.int32)
    packed = system_logits(params, cfg, row, seg)[0]
    np.testing.assert_allclose(packed[:21], system_logits(params, cfg, a),
                               **TOL)
    np.testing.assert_allclose(packed[21:58], system_logits(params, cfg, b),
                               **TOL)
    # and with the boundary left off it is another model
    merged = system_logits(params, cfg, row, (seg > 0).astype(jnp.int32))[0]
    assert float(jnp.abs(merged[21:58] - packed[21:58]).max()) > 1e-2


# ---- (d) the hand-over: one scan a run, the sources' tensors kept once ----

def unrolled(cfg, params, h, seg, impl="reference"):
    """The layers one by one through ``_block``, the hand-over by hand."""
    seen, shared = {}, {}
    for i, kind in enumerate(cfg.layer_kinds):
        j = seen.get(kind, 0)
        seen[kind] = j + 1
        lp = {k: w[j] for k, w in params["layers"][kind].items()}
        h, made, _ = transformer._block(
            cfg, h, lp, None, None, seg, POS[:, :h.shape[1]], None, None,
            None, impl, kind=kind, shared=shared, layer_index=i)
        if cfg.handed_on_by(i):
            shared[cfg.handed_on_by(i)] = made
    return h


def weigh(out):
    return jnp.sum(out * jnp.cos(jnp.arange(out.size).reshape(out.shape)))


@functools.lru_cache(maxsize=None)
def a_row_of_two_documents():
    """The model, a row of two documents, and the unrolled layers' loss and
    gradients on it (once for every ``remat``)."""
    cfg, params = model()
    h0 = params["embedding"][tokens(4, 64)][None]
    seg = jnp.asarray([[1] * 30 + [2] * 34], jnp.int32)
    want = jax.jit(jax.value_and_grad(
        lambda p, h: weigh(unrolled(cfg, p, h, seg)), argnums=(0, 1)))(
            params, h0)
    return cfg, params, h0, seg, want


@pytest.mark.parametrize("remat", [False, "full", "attention", "matmuls"])
def test_the_scanned_runs_equal_the_unrolled_layers(remat):
    cfg, params, h0, seg, (want_l, want_g) = a_row_of_two_documents()

    def scanned(p, h):
        return weigh(transformer.apply_layer_stack(
            cfg, h, p["layers"], None, None, seg, POS,
            attn_impl="reference", remat=remat)[0])

    got_l, got_g = jax.jit(jax.value_and_grad(scanned, argnums=(0, 1)))(
        params, h0)
    np.testing.assert_allclose(got_l, want_l, rtol=1e-4)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got_g),
                            jax.tree.leaves(want_g)):
        if "'bk'" in jax.tree_util.keystr(path):
            continue  # zero but for rounding: a softmax does not see it
        scale = float(jnp.abs(w).max()) + 1e-9
        np.testing.assert_allclose(g, w, atol=2e-3 * scale, rtol=2e-3,
                                   err_msg=jax.tree_util.keystr(path))


def test_the_pattern_traces_one_copy_of_each_unit():
    """``(M,S)x2 . M . F . (G,X)x2`` is four runs: the program holds TWO
    selective scans (the repeated unit's and the source's) for three M
    layers, and three attention calls (S, F, X) for five."""
    cfg, params = model()
    assert transformer.period_runs(cfg.period_kinds)[0] == ((S6, SLIDING), 2)
    h0 = params["embedding"][tokens(5, 64)][None]
    seg = jnp.ones((1, 64), jnp.int32)
    scans = sum(ssm.s6_geometry_counts().values())
    with attention.dispatch_label("sambay-units"):
        jax.make_jaxpr(lambda p, h: transformer.apply_layer_stack(
            cfg, h, p["layers"], None, None, seg, POS,
            attn_impl="reference")[0])(params, h0)
    assert sum(ssm.s6_geometry_counts().values()) - scans == 2
    assert attention.dispatch_counts()["sambay-units"] == {"reference": 3}


@pytest.mark.parametrize("what", [MEMORY, SHARED_KV])
def test_a_sources_gradient_sums_over_every_reader(what):
    """d loss / d (what a source handed on) is the sum, over the layers
    that read it, of each one's own."""
    cfg, params = model()
    h0 = params["embedding"][tokens(6, 32)][None]
    seg = jnp.ones((1, 32), jnp.int32)
    readers = [i for i, k in enumerate(cfg.layer_kinds)
               if k == (GMU if what == MEMORY else CROSS)]

    def run(eps):  # eps: {reader: perturbation of what IT reads}
        seen, shared, h = {}, {}, h0
        for i, kind in enumerate(cfg.layer_kinds):
            j = seen.get(kind, 0)
            seen[kind] = j + 1
            lp = {k: w[j] for k, w in params["layers"][kind].items()}
            mine = dict(shared)
            if i in eps:
                mine[what] = jax.tree.map(jnp.add, shared[what], eps[i])
            h, made, _ = transformer._block(
                cfg, h, lp, None, None, seg, POS[:, :32], None, None, None,
                "reference", kind=kind, shared=mine, layer_index=i)
            if cfg.handed_on_by(i):
                shared[cfg.handed_on_by(i)] = made
        return jnp.sum(h * h), shared[what]

    _, made = run({})
    zero = jax.tree.map(jnp.zeros_like, made)
    # eager: as one program each, XLA's other order of the float32 sums
    # is outside the tolerance below
    each = jax.grad(lambda eps: run(eps)[0])({i: zero for i in readers})
    total = jax.grad(lambda e: run({i: e for i in readers})[0])(zero)
    for got, *parts in zip(jax.tree.leaves(total),
                           *(jax.tree.leaves(each[i]) for i in readers)):
        assert all(float(jnp.abs(p).max()) > 0 for p in parts)
        np.testing.assert_allclose(got, sum(parts), rtol=1e-5, atol=1e-6)


def test_the_scopes_the_benchmark_reads_are_the_programs():
    from areal_tpu.base import telemetry
    from benchmark import sambay_trace

    assert sambay_trace.SCOPES == telemetry.SAMBAY_SCOPES


def test_what_a_source_hands_on_is_kept_once():
    """remat_kept_bytes: the memory and the K/V are kept under every
    entry, once — not a copy a reader."""
    cfg, _ = model()
    kept = transformer.remat_kept_bytes(cfg, tokens=64, itemsize=2)
    handed = 64 * 2 * (cfg.s6.d_inner + 3 * cfg.kv_dim)
    assert kept["full"] == cfg.n_layers * 64 * 64 * 2 + handed
    assert kept["full"] <= kept["attention"] <= kept["matmuls"]


# ---- (e) the selective scan ----

def recurrence(x, dt, A, Bm, Cm, D, seg):
    """A token at a time, the state zeroed at a document's first token."""
    first = seg != jnp.concatenate(
        [jnp.full_like(seg[:, :1], -1), seg[:, :-1]], 1)

    def token(h, at):
        x_t, dt_t, B_t, C_t, first_t = at
        h = jnp.where(first_t[:, None, None], 0.0, h)
        h = (jnp.exp(dt_t[..., None] * A) * h
             + (dt_t * x_t)[..., None] * B_t[:, None, :])
        return h, jnp.einsum("bdn,bn->bd", h, C_t) + D * x_t

    by_token = [jnp.moveaxis(a, 1, 0) for a in (x, dt, Bm, Cm, first)]
    ys = jax.lax.scan(token, jnp.zeros((x.shape[0],) + A.shape), by_token)[1]
    return jnp.moveaxis(ys, 0, 1)


@pytest.mark.parametrize("impl,T", [("reference", 150), ("reference", 128),
                                    ("pallas_interpret", 128),
                                    ("pallas_interpret", 100)])
def test_the_chunked_scan_and_its_backward_match_the_recurrence(impl, T):
    k = jax.random.split(jax.random.PRNGKey(7), 7)
    B_, Dn, N = 2, 128, 16
    x = jax.random.normal(k[0], (B_, T, Dn))
    dt = jax.nn.softplus(jax.random.normal(k[1], (B_, T, Dn))) * 0.3
    A = -jnp.exp(0.5 * jax.random.normal(k[2], (Dn, N)))
    Bm = jax.random.normal(k[3], (B_, T, N))
    Cm = jax.random.normal(k[4], (B_, T, N))
    D = jax.random.normal(k[5], (Dn,))
    a, b = T // 3, T - T // 8  # three documents, and padding, in row 0
    seg = jnp.asarray([[1] * a + [2] * (b - a) + [0] * (T - b), [1] * T],
                      jnp.int32)
    w = jax.random.normal(k[6], (B_, T, Dn)) * (seg > 0)[..., None]
    args = (x, dt, A, Bm, Cm, D)
    want, want_g = jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(recurrence(*a, seg) * w), argnums=range(6)))(*args)
    got, got_g = jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(ssm.selective_scan(*a, seg, impl) * w),
        argnums=range(6)))(*args)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for name, g, wg in zip("x dt A B C D".split(), got_g, want_g):
        if g.ndim == 3:  # padding's gradient is nobody's
            g, wg = g * (seg > 0)[..., None], wg * (seg > 0)[..., None]
        np.testing.assert_allclose(g, wg, rtol=1e-4, atol=1e-4 * float(
            jnp.abs(wg).max()), err_msg=name)


def test_the_scan_keeps_the_chunks_entering_states_and_nothing_longer():
    """No [T, d_inner, N] array in the forward or the backward program:
    the largest array either holds is a chunk's."""
    B_, T, Dn, N = 1, 512, 128, 16
    spec = jax.ShapeDtypeStruct
    args = (spec((B_, T, Dn), jnp.float32), spec((B_, T, Dn), jnp.float32),
            spec((Dn, N), jnp.float32), spec((B_, T, N), jnp.float32),
            spec((B_, T, N), jnp.float32), spec((Dn,), jnp.float32))
    seg = jnp.ones((B_, T), jnp.int32)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(ssm.selective_scan(*a, seg, "reference")),
        argnums=(0, 1, 2)))(*args)

    def sizes(jp):
        for eqn in jp.eqns:
            for v in eqn.outvars:
                yield int(np.prod(v.aval.shape))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from sizes(sub)

    assert max(sizes(jaxpr.jaxpr)) <= ssm.S6_CHUNK * Dn * N
    assert T * Dn * N > 4 * ssm.S6_CHUNK * Dn * N


# ---- (f) names, and the refusals by name ----

def test_hf_names_round_trip():
    cfg, params = model()
    sd = hf.params_to_hf_state_dict(params, cfg)
    assert "model.layers.5.attn.Wqkv.weight" in sd  # F: [q | k | v]
    assert sd["model.layers.5.attn.Wqkv.weight"].shape == (64 + 2 * 32, 64)
    assert sd["model.layers.7.attn.Wqkv.weight"].shape == (64, 64)  # X: q
    assert sd["model.layers.0.attn.A_log"].shape == (128, 16)
    assert sd["model.layers.6.attn.in_proj.weight"].shape == (128, 64)  # G
    assert "lm_head.weight" not in sd  # tied
    back = hf.params_from_hf_state_dict(sd, cfg)
    want, got = hf.flatten_pytree(params), hf.flatten_pytree(back)
    assert sorted(want) == sorted(got)
    for name in want:
        np.testing.assert_array_equal(np.asarray(want[name]),
                                      np.asarray(got[name]), err_msg=name)


@pytest.mark.parametrize("where", ["ring", "pipeline", "generate"])
def test_where_the_new_kinds_cannot_go_yet_is_refused_by_name(where):
    cfg, params = model()
    if where == "ring":
        from areal_tpu.parallel import ring

        assert ring.ring_refusal(cfg) == "selective_scan"
        assert "selective_scan" in ring.RING_REFUSALS
    elif where == "pipeline":
        from jax.sharding import Mesh

        from areal_tpu.parallel import pipeline

        mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("pp",))
        pipeline._WARNED_FALLBACKS.discard("cross_layer_state")
        assert pipeline.pick_pp_microbatches(mesh, cfg, 4) is None
        assert "cross_layer_state" in pipeline._WARNED_FALLBACKS
        assert "cross_layer_state" in pipeline._FALLBACK_HINTS
    else:
        from areal_tpu.models import generate

        assert generate.decode_refusal(cfg).startswith(
            "recurrent_decode_state")
        with pytest.raises(NotImplementedError, match="recurrent_decode_state"):
            transformer.init_kv_cache(cfg, 1, 8)
        with pytest.raises(NotImplementedError, match="recurrent_decode_state"):
            transformer.forward(params, cfg, tokens()[None], POS[:, :43],
                                segment_ids=jnp.ones((1, 43), jnp.int32))
