"""``reference_nemotron_h`` against cases worked by hand (the convolution's
taps, a two-token recurrence, the gated group norm, the sigmoid router's
choice by score + bias, a two-matmul relu² expert behind the latent
projections), ``ssm_cost``'s functions at the published sizes, and the new
readers on plain data."""

import json
import math
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness, peaks, ssm_cost, ssm_trace
from benchmark import reference_nemotron_h as ref


def published():
    with open(os.path.join(harness.BENCH_DIR, "configs",
                           "nemotron-3-super-120b-a12b.json")) as f:
        return json.load(f)


def test_the_convolution_is_causal_and_reads_zero_before_the_document():
    x = jnp.asarray([[1.0], [2.0], [3.0], [4.0], [5.0]])
    w = jnp.asarray([[1000.0], [100.0], [10.0], [1.0]])  # w[3]: the token
    got = ref.conv(x, w, jnp.asarray([0.5]))
    want = [1.5, 12.5, 123.5, 1234.5, 2345.5]
    np.testing.assert_allclose(got[:, 0], want)


def test_two_tokens_of_the_recurrence_by_hand():
    x = jnp.asarray([[[2.0]], [[3.0]]])  # [T=2, H=1, P=1]
    dt = jnp.asarray([[0.5], [0.25]])
    A = jnp.asarray([-2.0])
    Bm = jnp.asarray([[[1.0, 2.0]], [[3.0, 4.0]]])
    Cm = jnp.asarray([[[1.0, 1.0]], [[1.0, -1.0]]])
    y = ref.scan(x, dt, A, Bm, Cm)
    s0 = 0.5 * 2.0 * np.asarray([1.0, 2.0])
    s1 = math.exp(-0.5) * s0 + 0.25 * 3.0 * np.asarray([3.0, 4.0])
    np.testing.assert_allclose(y[:, 0, 0], [s0.sum(), s1[0] - s1[1]],
                               rtol=1e-6)


def test_the_gate_comes_first_and_the_norm_spans_a_group():
    y = jnp.asarray([[3.0, 4.0, 6.0, 8.0]])
    z = jnp.asarray([[0.0, 0.0, 100.0, 100.0]])  # silu(0) = 0, silu(100) = 100
    w = jnp.asarray([1.0, 1.0, 2.0, 2.0])
    # group 0 is gated to zero (0 · rsqrt(0 + 0) would be nan: eps 1e-9)
    got = ref.gated_norm(y, z, w, 2, 1e-9)
    g1 = np.asarray([600.0, 800.0])
    np.testing.assert_allclose(got[0, :2], [0.0, 0.0], atol=1e-6)
    np.testing.assert_allclose(
        got[0, 2:], 2.0 * g1 / math.sqrt(np.mean(g1 * g1)), rtol=1e-5)


def test_the_choice_is_by_score_plus_bias_and_the_gates_are_scores():
    logit = jnp.log(jnp.asarray([[0.9, 0.6, 0.5, 0.1]])
                    / (1 - jnp.asarray([[0.9, 0.6, 0.5, 0.1]])))
    scores = 1 / (1 + jnp.exp(-logit))
    bias = jnp.asarray([0.0, 0.0, 0.2, 0.0])  # lifts expert 2 over expert 1
    g = ref.gates(scores, bias, 2, True, 5.0)
    np.testing.assert_allclose(
        g[0], [5 * 0.9 / 1.4, 0.0, 5 * 0.5 / 1.4, 0.0], rtol=1e-5)
    g = ref.gates(scores, bias * 0, 2, False, 1.0)
    np.testing.assert_allclose(g[0], [0.9, 0.6, 0.0, 0.0], rtol=1e-5)


def test_an_expert_layer_by_hand_on_a_share():
    cfg = {"n_routed_experts": 1, "num_routed_experts": 2,
           "expert_shard_index": 1, "num_experts_per_tok": 1,
           "norm_topk_prob": True, "routed_scaling_factor": 5.0,
           "mlp_hidden_act": "relu2"}
    u = jnp.asarray([[1.0, -2.0], [-1.0, 0.5]])
    lp = {
        "router": jnp.asarray([[-10.0, 10.0], [0.0, 0.0]]),  # u0 > 0: expert 1
        "router_bias": jnp.zeros(2),
        "latent_down": jnp.asarray([[1.0], [1.0]]),  # v = u0 + u1
        "latent_up": jnp.asarray([[2.0, 3.0]]),
        "e_up": jnp.asarray([[[1.0, -1.0]]]),  # the held expert: index 1
        "e_down": jnp.asarray([[[1.0], [10.0]]]),
        "s_up": jnp.asarray([[1.0], [0.0]]), "s_down": jnp.asarray([[1.0, 0.0]]),
    }
    # token 0 chose expert 1 (held): v = -1 → relu² of (-1, 1) = (0, 1) →
    # 10, gate 5 (a single choice renormalises to 1) → 50 → (100, 150);
    # token 1 chose expert 0, held elsewhere: nothing
    np.testing.assert_allclose(ref.routed(u, cfg, lp),
                               [[100.0, 150.0], [0.0, 0.0]], rtol=1e-5)
    np.testing.assert_allclose(ref.shared(u, cfg, lp),
                               [[1.0, 0.0], [0.0, 0.0]], rtol=1e-6)


def test_the_cut_adds_up_and_no_width_is_cut():
    cfg = published()
    assert ssm_cost.layer_counts(cfg) == {"M": 5, "E": 5, "*": 1}
    assert ssm_cost.share_params(cfg) == pytest.approx(422.9e6, rel=1e-3)
    d = cfg["hidden_size"]
    di = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    conv = di + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    mamba = (d * (di + conv + cfg["mamba_num_heads"]) + 5 * conv
             + 3 * cfg["mamba_num_heads"] + di + di * d + d)
    attn = 2 * d * 128 * (cfg["num_attention_heads"]
                          + cfg["num_key_value_heads"]) + d
    lat, f = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    outside = (d * 512 + 512 + 2 * d * lat
               + 2 * d * cfg["moe_shared_expert_intermediate_size"] + d)
    experts = cfg["n_routed_experts"] * 2 * lat * f
    total = (5 * mamba + attn + 5 * (outside + experts)
             + 2 * cfg["vocab_size"] * d + d)
    assert total == pytest.approx(700.9e6, rel=1e-4)
    assert mamba == pytest.approx(13.71e6, rel=1e-3)
    assert outside == pytest.approx(54.53e6, rel=1e-3)
    assert experts / 8 == pytest.approx(5.505e6, rel=1e-3)
    for key, whole in cfg["reduced_from"].items():
        assert key in cfg["reduced"] or key == "hybrid_override_pattern"
        assert cfg[key] != whole
    assert not [k for k in cfg["reduced"] if k.endswith(("_dim", "_size"))
                and k != "vocab_size"]


def test_scan_and_expert_costs():
    ops, nbytes = ssm_cost.ssd_scan_cost(1, 4096, 128, 16, 64, 1, 128, False)
    per_chunk = 2 * 128 * 128 * 128 + 16 * (
        2 * 128 * 128 * 64 + 4 * 128 * 64 * 128 + 2 * 64 * 128)
    assert ops == 32 * per_chunk
    assert nbytes == 2 * (2 * 4096 * 1024 + 2 * 4096 * 128) + 4 * 4096 * 16
    ops_b, bytes_b = ssm_cost.ssd_scan_cost(1, 4096, 128, 16, 64, 1, 128, True)
    assert ops_b == 2 * ops and bytes_b > nbytes
    # a row that is no multiple of the chunk pays for its last chunk whole
    assert ssm_cost.ssd_scan_cost(1, 4097, 128, 16, 64, 1, 128, False)[0] == (
        33 * per_chunk)
    ops, nbytes = ssm_cost.latent_ffn_cost(1000, 2, 8, 1024, 2688, False)
    assert ops == 4 * 1000 * 1024 * 2688
    assert nbytes == 2 * (2 * 1000 * 1024 + 2 * 8 * 2 * 1024 * 2688)
    # 11 GFLOP over 97 MB: 113 operations a byte, under the chip's ridge
    assert peaks.least_time(ops, nbytes, "TPU v5 lite")[1] == "memory"


def test_readers_on_plain_data_and_on_a_program_without_the_scopes():
    assert ssm_trace.scope_of(
        "jit(train_grad_sliced)/transpose(jvp(layer_scan))/while/body/"
        "checkpoint/ssm_scan/dot_general") == "ssm_scan"
    assert ssm_trace.scope_of(
        "jit(f)/layer_scan/moe/shared_expert/dot_general") == "shared_expert"
    assert ssm_trace.scope_of(
        "jit(f)/layer_scan/moe/moe_dispatch/sort") == "moe_dispatch"
    assert ssm_trace.scope_of("jit(f)/layer_scan/mlp/dot_general") is None
    none = {"counters": {}, "trace": {}}
    for read in (ssm_trace.ssm_scan_roofline, ssm_trace.latent_experts_roofline,
                 ssm_trace.latent_local_rows_pct):
        assert read(none) is None
    assert ssm_trace.scope_busy_pct(none, "ssm_scan") is None
    rec = {"counters": {"moe_routed_rows": 1000.0, "moe_local_rows": 16.0}}
    assert ssm_trace.latent_local_rows_pct(rec) == pytest.approx(1.6)
    for name in ("ssm_scan_busy_pct", "ssm_scan_roofline", "ssm_proj_busy_pct",
                 "latent_experts_busy_pct", "latent_experts_roofline",
                 "latent_route_busy_pct", "shared_expert_busy_pct",
                 "latent_local_rows_pct"):
        assert harness.metric_reader(name)(none) is None
