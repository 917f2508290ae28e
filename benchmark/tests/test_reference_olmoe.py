"""``reference_olmoe`` against cases worked by hand, ``moe_cost`` against
the published sizes, and ``moe_trace``'s reduction on plain data."""

import json
import os

import jax.numpy as jnp
import numpy as np

from benchmark import harness, moe_cost, moe_trace
from benchmark import reference_olmoe as ref


def test_gates_keep_the_top_k_probabilities_as_they_are():
    p = jnp.asarray([[0.5, 0.1, 0.3, 0.1], [0.05, 0.15, 0.2, 0.6]])
    np.testing.assert_allclose(
        ref.gates(p, 2, False), [[0.5, 0, 0.3, 0], [0, 0, 0.2, 0.6]])
    np.testing.assert_allclose(
        ref.gates(p, 2, True),
        [[0.625, 0, 0.375, 0], [0, 0, 0.25, 0.75]], rtol=1e-6)


def test_two_experts_by_hand():
    """Two tokens of width 2, two experts of width 1, each token gated to
    one expert: y = g * down * silu(gate . x) * (up . x), written out."""
    x = jnp.asarray([[1.0, 2.0], [3.0, -1.0]])
    w_gate = jnp.asarray([[[1.0], [0.0]], [[0.0], [1.0]]])   # [E, D, F]
    w_up = jnp.asarray([[[1.0], [1.0]], [[2.0], [0.0]]])
    w_down = jnp.asarray([[[1.0, -1.0]], [[0.5, 2.0]]])      # [E, F, D]
    g = jnp.asarray([[0.7, 0.0], [0.0, 0.4]])

    def silu(v):
        return v / (1.0 + np.exp(-v))

    # token 0 → expert 0: gate.x = 1, up.x = 3; token 1 → expert 1:
    # gate.x = -1, up.x = 6
    want = np.asarray([
        0.7 * silu(1.0) * 3.0 * np.asarray([1.0, -1.0]),
        0.4 * silu(-1.0) * 6.0 * np.asarray([0.5, 2.0]),
    ])
    np.testing.assert_allclose(ref.experts(x, g, w_gate, w_up, w_down), want,
                               rtol=1e-6)


def test_one_layer_by_hand():
    """A whole one-layer model of width 2 with one head and one token: the
    softmax over one position is 1, so attention returns v Wo; rope at
    position 0 is the identity; the q/k norm cannot matter."""
    cfg = {"num_hidden_layers": 1, "hidden_size": 2, "num_attention_heads": 1,
           "num_key_value_heads": 1, "rms_norm_eps": 0.0, "rope_theta": 1e4,
           "num_experts_per_tok": 1, "norm_topk_prob": False,
           "tie_word_embeddings": False}
    eye = jnp.eye(2)
    L = {"ln1": jnp.ones((1, 2)), "ln2": jnp.ones((1, 2)),
         "wq": eye[None], "wk": eye[None], "wv": 2 * eye[None],
         "wo": eye[None], "q_norm": jnp.ones((1, 2)),
         "k_norm": jnp.ones((1, 2)),
         "router": jnp.asarray([[[5.0, 0.0], [0.0, 0.0]]]),
         "e_gate": jnp.ones((1, 2, 2, 1)), "e_up": jnp.ones((1, 2, 2, 1)),
         "e_down": jnp.stack([jnp.ones((1, 2)), -jnp.ones((1, 2))])[None]}
    params = {"embedding": jnp.asarray([[3.0, 4.0]]), "layers": L,
              "final_ln": jnp.ones(2), "lm_head": eye}
    h0 = np.asarray([3.0, 4.0])
    rms = lambda v: v / np.sqrt(np.mean(v * v))  # noqa: E731
    h1 = h0 + 2 * rms(h0)                       # v = 2 rms(h), Wo = I
    x = rms(h1)
    p = np.exp([5 * x[0], 0.0]) / np.exp([5 * x[0], 0.0]).sum()
    s = x.sum()
    h2 = h1 + p[0] * (s / (1 + np.exp(-s))) * s * np.ones(2)  # expert 0
    np.testing.assert_allclose(
        ref.logits(params, cfg, jnp.asarray([0]))[0], rms(h2), rtol=1e-5)


def test_costs_at_the_published_sizes():
    with open(os.path.join(harness.BENCH_DIR, "configs",
                           "olmoe-1b-7b.json")) as f:
        cfg = json.load(f)
    assert moe_cost.expert_width(cfg) == 1024
    per_layer = 4 * 2048 * 2048 + 2048 * 64 + 8 * 3 * 2048 * 1024
    assert moe_cost.activated_matmul_params(cfg) == (
        4 * per_layer + 2048 * 50304)
    ops, nbytes = moe_cost.grouped_ffn_cost(32768, 1, 16, 2048, 1024, False)
    assert ops == 3 * 2 * 32768 * 2048 * 1024
    assert nbytes == 2 * (2 * 32768 * 2048 + 16 * 3 * 2048 * 1024)
    ops_b, bytes_b = moe_cost.grouped_ffn_cost(32768, 1, 16, 2048, 1024, True)
    assert ops_b == 2 * ops and bytes_b > nbytes


def test_moe_trace_reduction_on_plain_data():
    ops = [(0.0, 1.0, "fusion.1"), (1.0, 3.0, "select_fusion.2"),
           (3.0, 3.5, "all-gather.3"), (3.5, 4.0, "all-reduce.4"),
           (4.0, 5.0, "fusion.5")]
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": [(0.0, 5.0, "jit_train_grad(7)")]},
    ]}]
    names = {
        ("7", "fusion.1"): "jit(train_grad)/layer_scan/moe/moe_router/dot",
        ("7", "select_fusion.2"):
            "jit(train_grad)/transpose(jvp(moe))/moe_experts/select_n",
        ("7", "all-gather.3"): "jit(train_grad)/moe/moe_exchange/all_gather",
        ("7", "all-reduce.4"): "jit(train_grad)/grad_accum/psum",
        ("7", "fusion.5"): "jit(train_grad)/mlp/dot",
    }
    red = moe_trace.reduce_planes(planes, names)
    assert red["busy_s"] == 5.0
    assert red["scopes"] == {"moe_router": 1.0, "moe_experts": 2.0,
                             "moe_exchange": 0.5}
    assert red["collectives"] == {"all-gather": 0.5, "all-reduce": 0.5}
    # a program without the scopes (the parent): nothing to read
    assert moe_trace.reduce_planes(planes, {})["scopes"] is None
    assert moe_trace.reduce_planes(planes, None)["scopes"] is None
    assert moe_trace.reduce_planes([], names) == {}
    # the grouped GEMM's custom call keeps no framework name: by op name
    planes[0]["lines"][0]["events"].append((5.0, 6.0, "ragged-dot-none.9"))
    assert moe_trace.reduce_planes(planes, names)["scopes"][
        "moe_experts"] == 3.0
