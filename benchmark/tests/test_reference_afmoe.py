"""``reference_afmoe`` against cases worked by hand at tiny sizes (the
gate, the post-norms, the renormalised scaled gates with the choice by
score + bias, the window's edge ``i - j < window``, RoPE on the sliding
kind only), ``afmoe_trace``'s counts at the published sizes, and the new
readers on plain data."""

import json
import math
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import afmoe_trace, harness
from benchmark import reference_afmoe as ref


def published():
    with open(os.path.join(harness.BENCH_DIR, "configs",
                           "trinity-mini.json")) as f:
        return json.load(f)


def test_the_window_ends_at_i_minus_j_less_than_window():
    # one head of 1: softmax weights over the keys a query may see
    T = 5
    q = jnp.zeros((T, 1, 1))
    k = jnp.zeros((T, 1, 1))
    v = jnp.arange(1.0, T + 1).reshape(T, 1, 1)  # the key's position + 1
    got = ref.attention(q, k, v, window=2)[:, 0, 0]
    # query i sees keys i-1 and i: the mean of their values
    np.testing.assert_allclose(got, [1.0, 1.5, 2.5, 3.5, 4.5])
    full = ref.attention(q, k, v)[:, 0, 0]
    np.testing.assert_allclose(full, [1.0, 1.5, 2.0, 2.5, 3.0])


def test_the_choice_is_by_score_plus_bias_and_the_gates_renormalised_scaled():
    p = np.asarray([[0.9, 0.6, 0.5, 0.1]])
    router = jnp.asarray(np.log(p / (1 - p)))  # x = [1]: logits = router
    x = jnp.ones((1, 1))
    cfg = {"num_experts_per_tok": 2, "route_norm": True,
           "route_scale": 2.826, "score_func": "sigmoid"}
    bias = jnp.asarray([0.0, 0.0, 0.2, 0.0])  # lifts expert 2 over expert 1
    g = ref.gates(x, cfg, router, bias)
    np.testing.assert_allclose(
        g[0], [2.826 * 0.9 / 1.4, 0.0, 2.826 * 0.5 / 1.4, 0.0], rtol=1e-5)
    g = ref.gates(x, {**cfg, "route_norm": False, "route_scale": 1.0},
                  router, bias * 0)
    np.testing.assert_allclose(g[0], [0.9, 0.6, 0.0, 0.0], rtol=1e-5)


def tiny_block(kind, dense=False):
    """One block, hidden 2, one head of 2, by hand: the embedding picks
    h0, every projection is the identity."""
    eye = jnp.eye(2)
    lp = {"ln1": jnp.ones((1, 2)), "ln1_post": jnp.ones((1, 2)) * 3.0,
          "ln2": jnp.ones((1, 2)), "ln2_post": jnp.ones((1, 2)) * 0.5,
          "wq": eye[None], "wk": eye[None], "wv": eye[None], "wo": eye[None],
          "wg": (eye * 0.0)[None],  # gate logit 0: sigmoid 1/2
          "q_norm": jnp.ones((1, 2)), "k_norm": jnp.ones((1, 2))}
    if dense:
        lp.update(w_gate=eye[None] * 100.0, w_up=eye[None],
                  w_down=eye[None])
    else:
        lp.update(router=jnp.asarray([[[10.0], [0.0]]]),
                  router_bias=jnp.zeros((1, 1)),
                  e_gate=(eye * 100.0)[None, None], e_up=eye[None, None],
                  e_down=eye[None, None], s_gate=(eye * 100.0)[None],
                  s_up=eye[None], s_down=eye[None] * 2.0)
    cfg = {"num_hidden_layers": 1, "num_dense_layers": int(dense),
           "layer_types": [kind], "hidden_size": 2, "num_attention_heads": 1,
           "num_key_value_heads": 1, "head_dim": 2, "rms_norm_eps": 0.0,
           "rope_theta": 10000, "sliding_window": 4, "mup_enabled": True,
           "num_experts": 1, "num_experts_per_tok": 1, "route_norm": True,
           "route_scale": 2.826, "score_func": "sigmoid",
           "num_shared_experts": 1}
    params = {"embedding": jnp.asarray([[3.0, 4.0]]) / math.sqrt(2.0),
              "layers": lp, "final_ln": jnp.ones(2), "lm_head": eye}
    return params, cfg


def unit(v):
    v = np.asarray(v, np.float64)
    return v / math.sqrt(np.mean(v * v))


@pytest.mark.parametrize("dense", [True, False], ids=["dense", "experts"])
def test_one_block_by_hand_gate_post_norms_and_the_ffn(dense):
    """One token (attention returns its own v; position 0 turns nothing):
    h0 = E sqrt(2) = (3, 4); a = rms(h0) = unit(3, 4), halved by the
    gate's sigmoid(0); the post-norm renormalises and weighs by 3:
    h1 = h0 + 3 unit(3, 4). The FFN on x = unit(h1): silu(100 x) x ~ 100
    x^2 (dense: W2 the identity; experts: one routed expert whose gate
    is its score over itself x 2.826, and a shared expert whose W2 is
    twice the identity), and the post-norm (weight 1/2) takes the scale
    off: h2 = h1 + unit(x^2) / 2 either way."""
    params, cfg = tiny_block("full_attention", dense)
    h = np.asarray(ref.hidden(params, cfg, jnp.zeros((1,), jnp.int32)))[0]
    h1 = np.asarray([3.0, 4.0]) + 3.0 * unit([3.0, 4.0])
    x = unit(h1)
    np.testing.assert_allclose(h, h1 + 0.5 * unit(x * x), rtol=1e-5)
    # left out, each shows: no gate changes nothing here (the post-norm
    # renormalises a halved vector) but no post-norms does
    wrong = np.asarray(ref.hidden(params, cfg, jnp.zeros((1,), jnp.int32),
                                  frozenset({"no_post_norms"})))[0]
    h1w = np.asarray([3.0, 4.0]) + 0.5 * unit([3.0, 4.0])
    xw = unit(h1w)
    scale = 100.0 * (1.0 if dense else 2.826 + 2.0)
    np.testing.assert_allclose(wrong, h1w + scale * xw * xw, rtol=1e-4)


def test_rope_turns_the_sliding_kind_only():
    """Two tokens, v chosen so that the attention weights show: on a full
    layer q·k is position-free, on a sliding layer the second token's key
    and query are turned by one radian in the first pair."""
    params, cfg = tiny_block("full_attention")
    toks = jnp.zeros((2,), jnp.int32)
    full = np.asarray(ref.hidden(params, cfg, toks))
    slid = np.asarray(ref.hidden(
        params, {**cfg, "layer_types": ["sliding_attention"]}, toks))
    turned = np.asarray(ref.hidden(params, cfg, toks,
                                   frozenset({"rope_on_full"})))
    # same token twice: q = k = v at both positions, so attention returns
    # v whatever the weights, and the kinds agree on this input
    np.testing.assert_allclose(full, slid, rtol=1e-6)
    np.testing.assert_allclose(full, turned, rtol=1e-6)
    q = jnp.asarray([[[1.0, 0.0]], [[1.0, 0.0]]])
    r = np.asarray(ref._rope(q, 10000.0))
    np.testing.assert_allclose(r[0, 0], [1.0, 0.0], atol=1e-7)
    np.testing.assert_allclose(r[1, 0], [math.cos(1.0), math.sin(1.0)],
                               rtol=1e-6)


def test_counts_at_the_published_sizes():
    cfg = published()
    assert afmoe_trace.expert_layers(cfg) == 4
    attn = 2 * 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048
    assert attn == 27_262_976
    moe = 2048 * 128 + 3 * 2048 * 1024 + 8 * 8 / 128 * 3 * 2048 * 1024
    assert afmoe_trace.share_params(cfg) == int(
        5 * attn + 3 * 2048 * 6144 + 4 * moe + 2048 * 25088)


def test_readers_on_plain_data_and_on_a_program_without_the_scopes():
    assert afmoe_trace.scope_of(
        "jit(train_grad_sliced)/transpose(jvp(layer_scan))/while/body/"
        "checkpoint/o_proj/attn_gate/mul") == "attn_gate"
    assert afmoe_trace.scope_of(
        "jit(f)/layer_scan/moe/post_mlp_norm/rsqrt") == "post_mlp_norm"
    assert afmoe_trace.scope_of(
        "jit(f)/layer_scan/mlp/post_mlp_norm/rsqrt") == "post_mlp_norm"
    assert afmoe_trace.scope_of("jit(f)/layer_scan/mlp/dot_general") == "mlp"
    assert afmoe_trace.scope_of("jit(f)/layer_scan/moe/moe_router/dot") is None
    none = {"counters": {}, "trace": {}}
    assert afmoe_trace.experts_roofline(none) is None
    assert afmoe_trace.scope_busy_pct(none, "attn_gate") is None
    names = [m["name"] for m in harness.load_benchmark()["per_layer"]
             if m["name"].startswith("afmoe_")]
    assert len(names) == 11
    for name in names:
        assert harness.metric_reader(name)(none) is None
    rec = {"counters": {"moe_routed_rows": 1000.0, "moe_local_rows": 62.5}}
    assert harness.metric_reader("afmoe_local_rows_pct")(rec) == \
        pytest.approx(6.25)
    geo = {"counters": {"window_geometry": {
        "16384>16384/512/w2048": {"calls": 4, "blocks_visited": 40,
                                  "blocks_causal": 100, "rows": 1}}}}
    assert harness.metric_reader("afmoe_window_blocks_visited_pct")(geo) == \
        pytest.approx(40.0)
