"""``reference_qwen3_next`` against cases worked by hand at tiny sizes
(the delta rule's closed forms: with beta 1 and an orthonormal key the
state stores the value and returns it, a repeated key overwrites, the
gate decays before the delta is taken; the convolution's edge; the
zero-centred weight; partial rotary; the gates over all routed experts and
a share's part; the shared expert's gate), ``gdn_cost``'s counts at the
published sizes, and the new readers on plain data."""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import gdn_cost, gdn_trace, harness, peaks
from benchmark import reference_qwen3_next as ref

CELL = "qwen3-next-80b-a3b.train-longdoc-16k"
NEW_METRICS = (
    "gdn_rule_busy_pct", "gdn_rule_roofline", "gdn_proj_busy_pct",
    "gdn_glue_busy_pct", "qnext_attn_busy_pct", "qnext_attn_roofline",
    "qnext_experts_busy_pct", "qnext_experts_roofline",
    "qnext_route_busy_pct", "qnext_shared_expert_busy_pct",
    "qnext_local_rows_pct", "gdn_resets_in_chunk_per_row")


def published():
    with open(os.path.join(harness.BENCH_DIR, "configs",
                           "qwen3-next-80b-a3b.json")) as f:
        return json.load(f)


def test_the_rule_stores_a_value_under_its_key_and_returns_it():
    # one head, keys e0, e1, e0: beta 1, no decay. The third token
    # OVERWRITES what the first stored under e0.
    e = jnp.eye(2)
    k = jnp.stack([e[0], e[1], e[0]])[:, None]
    v = jnp.asarray([[1.0, 2.0], [3.0, 4.0], [5.0, 6.0]])[:, None]
    o = ref.delta_rule(k, k, v, jnp.zeros((3, 1)), jnp.ones((3, 1)))
    np.testing.assert_allclose(o[:, 0], v[:, 0], atol=1e-6)
    # a query for e1 after the third token still reads the second value
    q = jnp.stack([e[1]] * 3)[:, None]
    o = ref.delta_rule(q, k, v, jnp.zeros((3, 1)), jnp.ones((3, 1)))
    np.testing.assert_allclose(o[:, 0], [[0, 0], [3, 4], [3, 4]], atol=1e-6)


def test_the_gate_decays_the_state_before_the_delta_is_taken():
    # same key twice, beta 1/2, decay 1/2 a token:
    # S_0 = v0 / 2;  S_1 = S_0 / 2 + (v1 - S_0 / 2) / 2 = v0 / 8 + v1 / 2
    k = jnp.ones((2, 1, 1))
    v = jnp.asarray([8.0, 4.0])[:, None, None]
    o = ref.delta_rule(k, k, v, jnp.full((2, 1), -math.log(2.0)),
                       jnp.full((2, 1), 0.5))
    np.testing.assert_allclose(o[:, 0, 0], [4.0, 3.0], rtol=1e-6)
    rounded = ref.delta_rule(k, k, v * (1 + 2.0 ** -10),
                             jnp.zeros((2, 1)), jnp.ones((2, 1)),
                             frozenset({"state_in_bfloat16"}))
    np.testing.assert_allclose(rounded[:, 0, 0], [8.0, 4.0])  # 7 bits kept


def test_the_convolution_reads_zero_before_the_document_and_has_no_bias():
    x = jnp.arange(1.0, 6.0)[:, None]
    w = jnp.asarray([1.0, 10.0, 100.0, 1000.0])[:, None]
    np.testing.assert_allclose(
        ref.conv(x, w)[:, 0], [1000, 2100, 3210, 4321, 5432])
    np.testing.assert_array_equal(ref.conv(jnp.zeros((3, 2)), jnp.ones((4, 2))),
                                  0)


def test_the_norm_weight_is_zero_centred():
    x = jnp.asarray([[3.0, 4.0]])
    unit = x / math.sqrt(12.5)
    np.testing.assert_allclose(ref.rms(x, jnp.zeros(2), 0.0), unit, rtol=1e-6)
    np.testing.assert_allclose(
        ref.rms(x, jnp.asarray([1.0, -0.5]), 0.0), unit * jnp.asarray([2.0, 0.5]),
        rtol=1e-6)
    np.testing.assert_allclose(
        ref.rms(x, jnp.asarray([1.0, -0.5]), 0.0,
                frozenset({"norm_weight_without_1_plus"})),
        unit * jnp.asarray([1.0, -0.5]), rtol=1e-6)


def test_rope_turns_the_first_dims_and_leaves_the_others():
    x = jnp.ones((3, 1, 8))
    got = ref.rope(x, 100.0, 4)
    np.testing.assert_array_equal(got[..., 4:], 1.0)
    np.testing.assert_array_equal(got[0], 1.0)  # position 0: no turn
    # pairs (0, 2) at angle t and (1, 3) at angle t / 10
    for t in (1, 2):
        np.testing.assert_allclose(
            got[t, 0, :4],
            [math.cos(t) - math.sin(t), math.cos(t / 10) - math.sin(t / 10),
             math.cos(t) + math.sin(t), math.cos(t / 10) + math.sin(t / 10)],
            rtol=1e-5)


def test_the_gates_are_the_top_k_of_all_routed_experts_renormalised():
    cfg = {"num_experts_per_tok": 2, "norm_topk_prob": True}
    x = jnp.eye(2)
    router = jnp.log(jnp.asarray([[4.0, 3.0, 2.0, 1.0], [1.0, 1.0, 2.0, 6.0]]))
    g = ref.gates(x, cfg, router)
    np.testing.assert_allclose(g, [[4 / 7, 3 / 7, 0, 0], [0, 0, 0.25, 0.75]],
                               rtol=1e-5)
    raw = ref.gates(x, cfg, router, frozenset({"gates_not_renormalised"}))
    np.testing.assert_allclose(raw, [[0.4, 0.3, 0, 0], [0, 0, 0.2, 0.6]],
                               rtol=1e-5)


def test_a_share_adds_its_own_experts_part_and_the_shared_expert_is_gated():
    d, f = 2, 2
    cfg = {"num_experts_per_tok": 2, "norm_topk_prob": True,
           "num_experts": 2, "num_routed_experts": 4,
           "expert_shard_index": 1}
    assert ref.first_held(cfg) == 2
    ident = jnp.broadcast_to(jnp.eye(d), (2, d, f))
    lp = {"router": jnp.log(jnp.asarray([[4.0, 3.0, 2.0, 1.0],
                                         [1.0, 1.0, 2.0, 6.0]])),
          "e_gate": ident, "e_up": ident, "e_down": ident * jnp.asarray(
              [1.0, 10.0])[:, None, None],
          "s_gate": jnp.eye(d), "s_up": jnp.eye(d), "s_down": jnp.eye(d),
          "s_sig": jnp.zeros((d, 1))}
    x = jnp.eye(2)
    y = jax.nn.silu(x) * x  # every expert computes this, times its scale
    # token 0 chose experts 0 and 1 (held elsewhere): nothing here; token
    # 1 chose experts 2 and 3, which are this share's 0 and 1
    np.testing.assert_allclose(
        ref.routed(x, cfg, lp), [[0, 0], (0.25 * 1 + 0.75 * 10) * y[1]],
        rtol=1e-5)
    np.testing.assert_allclose(ref.shared(x, lp), 0.5 * y, rtol=1e-5)
    np.testing.assert_allclose(
        ref.shared(x, lp, frozenset({"no_shared_expert_gate"})), y, rtol=1e-5)


def test_the_period_and_the_counts_at_the_published_sizes():
    cfg = published()
    assert [ref.is_full(cfg, i) for i in range(4)] == [False] * 3 + [True]
    assert gdn_cost.layer_counts(cfg) == {"gdn": 3, "full": 1}
    assert gdn_cost.gdn_runs(cfg) == 1
    assert gdn_cost.gdn_runs({**cfg, "num_hidden_layers": 8}) == 2
    assert ref.gdn_sizes(cfg) == (16, 32, 128, 128, 4)
    for key, was in cfg["reduced_from"].items():
        assert cfg[key] < was
    assert cfg["num_experts"] * cfg["expert_shard_count"] == cfg[
        "num_routed_experts"] == cfg["reduced_from"]["num_experts"]
    # the N of 6·N·T: mixers and head whole, 10 x 16 / 512 of an expert
    d = 2048
    mixers = 3 * (d * (12288 + 64) + 4096 * d) + d * (2 * 4096 + 2 * 512
                                                      ) + 4096 * d
    moe = d * 512 + 3 * d * 512 + d + 10 * 16 / 512 * 3 * d * 512
    assert gdn_cost.share_params(cfg) == int(mixers + 4 * moe + d * 19072)
    # the rule: a chunk's blocks, the substitution and the state products
    ops, nbytes = gdn_cost.gdn_rule_cost(1, 16384, 16, 32, 128, 128, False)
    assert ops == 256 * (16 * 4 * 64 * 64 * 128 + 32 * (
        64 * 64 * 256 + 64 * 64 * 128 + 6 * 64 * 128 * 128))
    assert nbytes == 2 * (2 * 16384 * 2048 + 2 * 16384 * 4096) + 8 * 16384 * 32
    ops_b, bytes_b = gdn_cost.gdn_rule_cost(1, 16384, 16, 32, 128, 128, True)
    assert ops_b == 2 * ops and bytes_b > nbytes
    assert gdn_cost.attention_cost(cfg, 1, 8192, False) == (
        peaks.flash_attention_cost(1, 8192, 16, 2, 256, False))


def test_the_benchmark_declares_the_cell_and_its_twelve_metrics():
    bench = harness.load_benchmark()
    r = harness.resolve_cell(CELL, bench)
    assert r["cell"]["chips"] == 1 and len(r["cell"]["why"]) <= 200
    assert r["traffic"]["driver"] == "train_qwen3_next"
    assert r["traffic"]["compile_grid"] == [1, 16384]
    assert r["config"]["reference"] == "reference_qwen3_next"
    mine = {m["name"]: m for m in bench["per_layer"]
            if m.get("workloads") == [CELL]}
    assert set(mine) == set(NEW_METRICS)
    assert all(m["moves"] == "train_tok_s_chip" for m in mine.values())
    assert [m["name"] for m in bench["per_layer"]][-12:] == list(NEW_METRICS)
    for name in NEW_METRICS:
        assert callable(harness.metric_reader(name))


def test_the_readers_leave_the_line_on_a_program_without_the_scopes():
    records = {"trace": {}, "counters": {}, "device": {"kind": "TPU v5 lite"}}
    for name in NEW_METRICS:
        assert harness.metric_reader(name)(records) is None, name


def test_the_rooflines_are_the_least_time_over_the_measured_time(monkeypatch):
    cfg = published()
    rule = {"rows": 1, "length": 16384, "k_heads": 16, "v_heads": 32,
            "dk": 128, "dv": 128, "fwd": 3, "bwd": 1}
    records = {"trace": {"ops": {"splash_mqa_fwd_x": 0.02,
                                 "splash_mqa_dkv_x": 0.03,
                                 "splash_mqa_dq_x": 0.05}},
               "config": cfg, "device": {"kind": "TPU v5 lite"},
               "counters": {"gdn_rule_calls_traced": [rule],
                            "qnext_attn_calls_traced": [
                                {"rows": 1, "length": 8192, "fwd": 2,
                                 "bwd": 1}],
                            "gdn_resets_in_chunk_per_row": 0.75}}
    monkeypatch.setattr(gdn_trace, "load", lambda r: {
        "busy_s": 2.0, "scopes": {"gdn_rule": 0.5, "gdn_conv": 0.1}})
    least = sum(n * peaks.least_time(*gdn_cost.gdn_rule_cost(
        1, 16384, 16, 32, 128, 128, b), "TPU v5 lite")[0]
        for n, b in ((3, False), (1, True)))
    assert gdn_trace.rule_roofline(records) == pytest.approx(
        100 * least / 0.5)
    assert gdn_trace.scope_busy_pct(records, "gdn_rule") == 25.0
    assert gdn_trace.scope_busy_pct(records, "gdn_in_proj") is None
    least = sum(n * peaks.least_time(*peaks.flash_attention_cost(
        1, 8192, 16, 2, 256, b), "TPU v5 lite")[0]
        for n, b in ((2, False), (1, True)))
    assert gdn_trace.attn_roofline(records) == pytest.approx(100 * least / 0.1)
    assert gdn_trace.resets_in_chunk_per_row(records) == 0.75
    assert gdn_trace.scope_of("jit(f)/layer_scan/checkpoint/gdn_rule/while",
                              gdn_trace.SCOPES) == "gdn_rule"
