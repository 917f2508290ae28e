"""``reference_mellum2`` against cases worked by hand (one sliding and one
full layer at tiny sizes, the window's edge), ``window_trace``'s cost
functions at the published sizes, and the new readers on plain data."""

import json
import math
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness, moe_cost, peaks, window_trace
from benchmark import reference_mellum2 as ref

PLAIN = {"rope_type": "default", "rope_theta": 500000}
YARN = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}


def published():
    with open(os.path.join(harness.BENCH_DIR, "configs",
                           "mellum2-12b-a2.5b.json")) as f:
        return json.load(f)


def one_layer(kind, window=2, rope=None):
    """A one-layer model of width 2 with one head of 2 and ONE expert
    whose output is zero (``e_down`` 0): the layer is attention alone.
    Identity projections, so q = k = v = rms(h)."""
    cfg = {"num_hidden_layers": 1, "hidden_size": 2, "num_attention_heads": 1,
           "num_key_value_heads": 1, "head_dim": 2, "rms_norm_eps": 0.0,
           "num_experts": 1, "num_experts_per_tok": 1, "norm_topk_prob": True,
           "sliding_window": window, "layer_types": [kind],
           "rope_parameters": {"sliding_attention": rope or PLAIN,
                               "full_attention": rope or PLAIN}}
    eye = jnp.eye(2)
    L = {"ln1": jnp.ones((1, 2)), "ln2": jnp.ones((1, 2)),
         "wq": eye[None], "wk": eye[None], "wv": eye[None], "wo": eye[None],
         "router": jnp.zeros((1, 2, 1)),
         "e_gate": jnp.ones((1, 1, 2, 1)), "e_up": jnp.ones((1, 1, 2, 1)),
         "e_down": jnp.zeros((1, 1, 1, 2))}
    emb = jnp.asarray([[3.0, 4.0], [1.0, -1.0], [0.0, 2.0]])
    return cfg, {"embedding": emb, "layers": L, "final_ln": jnp.ones(2),
                 "lm_head": eye}


def rms(v):
    return v / np.sqrt(np.mean(v * v, -1, keepdims=True))


def rope_by_hand(x, pos, theta=500000.0, scale=1.0):
    """Heads of 2: one frequency, theta^0 = 1; rotate-half is a plain
    rotation by ``pos`` radians (times the attention factor)."""
    c, s = math.cos(pos) * scale, math.sin(pos) * scale
    return np.asarray([x[0] * c - x[1] * s, x[1] * c + x[0] * s])


def layer_by_hand(emb, window, scale=1.0):
    x = rms(emb)
    q = np.stack([rope_by_hand(v, t, scale=scale) for t, v in enumerate(x)])
    out = []
    for t in range(len(x)):
        lo = 0 if window is None else max(0, t - window + 1)
        s = np.asarray([q[t] @ q[u] for u in range(lo, t + 1)]) / math.sqrt(2)
        p = np.exp(s - s.max())
        p = p / p.sum()
        out.append(emb[t] + p @ x[lo:t + 1])
    return rms(np.stack(out))  # the expert adds 0; final norm; head = I


@pytest.mark.parametrize("kind,window", [("sliding_attention", 2),
                                         ("full_attention", None)])
def test_one_layer_by_hand(kind, window):
    """Three tokens, window 2: on the sliding layer the third token does
    not see the first, on the full layer it does."""
    cfg, params = one_layer(kind)
    emb = np.asarray(params["embedding"])
    got = ref.logits(params, cfg, jnp.asarray([0, 1, 2]))
    np.testing.assert_allclose(got, layer_by_hand(emb, window), rtol=1e-5)
    other = layer_by_hand(emb, None if window else 2)
    assert np.abs(np.asarray(got)[2] - other[2]).max() > 1e-3
    np.testing.assert_allclose(np.asarray(got)[:2], other[:2], rtol=1e-5)


def test_yarn_scales_the_tables_on_the_full_layer():
    """Heads of 2 have one dimension, below YaRN's ramp (low = 0 ... the
    frequency stays 1), so YaRN is the plain rotation times the attention
    factor on q and on k: scores grow by its square."""
    cfg, params = one_layer("full_attention", rope=YARN)
    emb = np.asarray(params["embedding"])
    got = ref.logits(params, cfg, jnp.asarray([0, 1, 2]))
    np.testing.assert_allclose(
        got, layer_by_hand(emb, None, scale=1.2772588722239782), rtol=1e-5)
    assert ref.attention_factor(YARN) == 1.2772588722239782
    assert ref.attention_factor({**YARN, "attention_factor": None}) == (
        pytest.approx(0.1 * math.log(16) + 1))
    assert ref.attention_factor(PLAIN) == 1.0


def test_yarn_frequencies_at_the_published_sizes():
    """low 18, high 35 (tests/test_mellum_parity.py works them out)."""
    f = np.asarray(ref.inv_freq(YARN, 128), np.float64)
    plain = np.asarray(ref.inv_freq(PLAIN, 128), np.float64)
    np.testing.assert_allclose(f[:19], plain[:19], rtol=1e-6)
    np.testing.assert_allclose(f[35:], plain[35:] / 16, rtol=1e-6)
    np.testing.assert_allclose(
        f[26], (9 / 17) * plain[26] + (8 / 17) * plain[26] / 16, rtol=1e-5)


@pytest.mark.parametrize("back,seen", [(1023, True), (1024, False)])
def test_the_windows_edge(back, seen):
    """Window 1024: the key 1023 back is seen, the key 1024 back is not."""
    T = 1100
    q = jnp.zeros((T, 1, 2))
    k = jnp.zeros((T, 1, 2))
    v = jnp.zeros((T, 1, 2)).at[1050 - back, 0, 0].set(1.0)
    out = ref.attention(q, k, v, window=1024)
    # uniform attention over the 1024 visible keys
    assert float(out[1050, 0, 0]) == pytest.approx(
        1 / 1024 if seen else 0.0, abs=1e-9)
    assert float(ref.attention(q, k, v)[1050, 0, 0]) == pytest.approx(
        1 / 1051)


def test_a_share_adds_only_the_held_experts_part():
    """Two of four experts held (2 and 3), top-2 renormalised over BOTH
    chosen: a token that chose experts 1 and 2 gets expert 2's part at its
    renormalised gate and nothing for expert 1."""
    x = jnp.asarray([[1.0, 0.0]])
    router = jnp.asarray([[0.0, 2.0, 1.0, -5.0], [0.0, 0.0, 0.0, 0.0]])
    cfg = {"num_experts": 2, "expert_shard_index": 1,
           "num_experts_per_tok": 2, "norm_topk_prob": True}
    ones = jnp.ones((2, 2, 1))
    down = jnp.asarray([[[1.0, 0.0]], [[0.0, 1.0]]])  # expert 2 -> x, 3 -> y
    y = ref.moe(x, cfg, router, ones, ones, down)
    p = np.exp([0.0, 2.0, 1.0, -5.0])
    p = p / p.sum()
    g2 = p[2] / (p[1] + p[2])
    silu1 = 1 / (1 + math.exp(-1.0))
    np.testing.assert_allclose(y, [[g2 * silu1 * 1.0, 0.0]], rtol=1e-6)
    assert ref.held_experts(cfg) == (2, 2)
    assert ref.held_experts(published()) == (0, 16)


# ---- the cost functions at the published sizes ----

def test_window_cost_at_the_published_sizes():
    cfg = published()
    nq, nkv, dh = 32, 4, 128
    assert (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["sliding_window"]) == (nq, nkv, dh, 1024)
    # an 8192-token row at tile 512: blocks at 0, 512 see 512 and 1024
    # keys, the other 14 see 1024 + 512
    pairs = 512 * (512 + 1024 + 14 * 1536)
    ops, nbytes = window_trace.window_attention_cost(
        1, 8192, 1024, 512, nq, nkv, dh, backward=False)
    assert ops == 4 * nq * pairs * dh
    assert nbytes == 2 * (2 * 8192 * nq * dh + 2 * 8192 * nkv * dh)
    b_ops, b_bytes = window_trace.window_attention_cost(
        1, 8192, 1024, 512, nq, nkv, dh, backward=True)
    assert b_ops == 2.5 * ops
    assert b_bytes == 2 * (5 * 8192 * nq * dh + 4 * 8192 * nkv * dh)
    # against the causal kernel's cost on the same row: 2.9 x less
    full, _ = peaks.flash_attention_cost(1, 8192, nq, nkv, dh, False)
    assert full / ops == pytest.approx(8192 * 8192 / 2 / pairs)
    assert 2.8 < full / ops < 3.0
    # compute-bound on a v5e, forward and backward
    assert peaks.least_time(ops, nbytes, "TPU v5 lite")[1] == "compute"


def test_share_params_at_the_published_sizes():
    cfg = published()
    attn = 2304 * 4096 + 2 * 2304 * 512 + 4096 * 2304
    moe = 2304 * 64 + 8 * 16 / 64 * 3 * 2304 * 896
    assert window_trace.share_params(cfg) == int(
        4 * (attn + moe) + 2304 * 24576)
    assert moe_cost.expert_width(cfg) == 896
    # the reckoned state: 595.2 M parameters
    held = 16 * 3 * 2304 * 896
    total = 4 * (attn + 2304 * 64 + held + 2 * 2304) + 2 * 24576 * 2304 + 2304
    assert total == pytest.approx(595.2e6, rel=1e-3)


# ---- the readers on plain data ----

def records(**counters):
    return {
        "device": {"kind": "TPU v5 lite"}, "chips": 1,
        "config": published(), "counters": counters,
        "trace": {"busy_s": 10.0, "ops": {
            "splash_mqa_fwd_segmented_residuals.3 f32[8,512,128]": 0.5,
            "splash_mqa_fwd_segmented_no_residuals f32[8,512,128]": 0.25,
            "splash_mqa_dkv_segmented_no_residuals.1 f32[8,512,128]": 0.75,
            "splash_mqa_dq_segmented_no_residuals f32[8,512,128]": 0.5,
            "flash_attention.2 bf16[1,32,6144,128]": 3.0,
            "fusion.7 bf16[8,128]": 1.0}},
    }


def test_window_readers_on_plain_data():
    assert window_trace.window_times(records()) == {
        "fwd": 0.75, "dkv": 0.75, "dq": 0.5}
    assert window_trace.window_attn_busy_pct(records()) == pytest.approx(20.0)
    # the flash readers' patterns do not take the windowed kernel's ops
    from benchmark import readers
    assert all(not readers.FLASH_FWD.search(n) and not
               readers.FLASH_BWD.search(n)
               for n in records()["trace"]["ops"] if n.startswith("splash"))
    geo = {"6016>6144/512/w1024": {"calls": 3, "blocks_visited": 99,
                                   "blocks_causal": 234}}
    assert window_trace.window_blocks_visited_pct(
        records(window_geometry=geo)) == pytest.approx(100 * 99 / 234)
    call = {"rows": 1, "length": 6016, "window": 1024, "tile": 512,
            "fwd": 36, "bwd": 18}
    got = window_trace.window_attn_roofline(
        records(window_calls_traced=[call]))
    f_ops, f_b = window_trace.window_attention_cost(
        1, 6016, 1024, 512, 32, 4, 128, False)
    b_ops, b_b = window_trace.window_attention_cost(
        1, 6016, 1024, 512, 32, 4, 128, True)
    least = (36 * peaks.least_time(f_ops, f_b, "TPU v5 lite")[0]
             + 18 * peaks.least_time(b_ops, b_b, "TPU v5 lite")[0])
    assert got == pytest.approx(100 * least / 2.0)
    assert 0 < got < 100


def test_share_readers_on_plain_data():
    r = records(moe_routed_rows=1000.0, moe_local_rows=251.0)
    assert window_trace.share_local_rows_pct(r) == pytest.approx(25.1)
    for metric in ("window_attn_busy_pct", "window_attn_roofline",
                   "window_blocks_visited_pct", "share_experts_busy_pct",
                   "share_experts_roofline", "share_route_busy_pct",
                   "share_local_rows_pct"):
        # a program without the kernel, the counters or a trace: nothing
        # to read, and no reader raises
        assert harness.metric_reader(metric)(
            {"counters": {}, "trace": {}}) is None, metric
        assert harness.metric_reader(metric)({}) is None, metric
