"""``reference_sambay`` against cases worked by hand at tiny sizes (the
recurrence's closed form under a constant decay, the window's edge, the
differential pair with its lambda, sub-norm and scale, the pattern and
its two sources), ``sambay_cost``'s counts at the published sizes, and the
new readers on plain data."""

import json
import math
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness, peaks, sambay_cost, sambay_trace
from benchmark import reference_sambay as ref


def published():
    with open(os.path.join(harness.BENCH_DIR, "configs",
                           "phi-4-mini-flash-reasoning.json")) as f:
        return json.load(f)


def test_the_recurrence_is_a_decayed_sum():
    # one channel, one state, constant Δ = 1, A = -ln 2: h_t = h_{t-1}/2 + x_t
    T = 6
    x = jnp.arange(1.0, T + 1)[:, None]
    y = ref.scan(x, jnp.ones((T, 1)), jnp.full((1, 1), -math.log(2.0)),
                 jnp.ones((T, 1)), 2.0 * jnp.ones((T, 1)))
    want = [2 * sum(2.0 ** -(t - s) * (s + 1) for s in range(t + 1))
            for t in range(T)]
    np.testing.assert_allclose(y[:, 0], want, rtol=1e-6)


def test_the_convolution_reads_zero_before_the_document():
    x = jnp.arange(1.0, 5.0)[:, None]
    w = jnp.asarray([[1.0], [10.0], [100.0], [1000.0]])  # w[3] on the token
    got = ref.conv(x, w, jnp.asarray([0.5]))[:, 0]
    np.testing.assert_allclose(got, [1000.5, 2100.5, 3210.5, 4321.5])


def test_the_window_ends_at_i_minus_j_less_than_window():
    assert np.asarray(ref.causal_mask(4, 2)).tolist() == [
        [True, False, False, False], [True, True, False, False],
        [False, True, True, False], [False, False, True, True]]
    assert np.asarray(ref.causal_mask(3, None)).sum() == 6


def test_a_differential_pair():
    # flat scores (q = 0): both softmaxes are the running mean of v;
    # o = (1 - lambda) mean, RMS-normed, times (1 - lambda_init)
    cfg = {"first_layer_index": 14, "layer_norm_eps": 0.0}
    T, d = 3, 2
    q = jnp.zeros((T, 2, d))
    k = jnp.ones((T, 2, d))
    v = jnp.asarray([[[3.0, 4.0, 0.0, 0.0]]] * T)
    lp = {"lambda_q1": jnp.zeros(d), "lambda_k1": jnp.zeros(d),
          "lambda_q2": jnp.zeros(d), "lambda_k2": jnp.zeros(d),
          "subln": jnp.ones(2 * d)}
    lam_init = 0.8 - 0.6 * math.exp(-0.3 * 14)
    assert ref.lambda_init_of(cfg, 0) == pytest.approx(lam_init)
    assert float(ref.lambda_of(lp, lam_init)) == pytest.approx(lam_init)
    got = ref.differential(q, k, v, cfg, lp, 0, None)
    # (1 - lambda) [3, 4, 0, 0] has RMS (1 - lambda) 2.5: the norm undoes
    # the (1 - lambda), the scale puts (1 - lambda_init) back
    np.testing.assert_allclose(
        got, np.tile(np.asarray([1.2, 1.6, 0, 0]) * (1 - lam_init), (T, 1)),
        rtol=1e-5)


def test_the_pattern_and_its_sources():
    cfg = published()
    assert ref.pattern_of(cfg) == "MSMFGX" == sambay_cost.pattern(cfg)
    assert (ref.memory_source("MSMFGX"), ref.kv_source("MSMFGX")) == (2, 3)
    whole = {**cfg, **cfg["reduced_from"], "layer_pattern": None}
    assert ref.pattern_of(whole) == cfg["reduced_from"]["layer_pattern"]
    assert sambay_cost.pattern(whole) == cfg["reduced_from"]["layer_pattern"]
    assert sambay_cost.layer_counts(whole) == {
        "M": 9, "S": 8, "F": 1, "G": 7, "X": 7}


def test_parameter_and_operation_counts_at_the_published_sizes():
    cfg = published()
    assert sambay_cost.s6_sizes(cfg) == (5120, 16, 160)
    # the file's arithmetic: 697.3 M with norms, biases and the scan's
    # vectors; the matrices alone are 0.3 M fewer
    assert round(sambay_cost.share_params(cfg) / 1e6, 1) == 697.0
    ops, nbytes = sambay_cost.selective_scan_cost(1, 8192, 5120, 16, False)
    assert ops == 6 * 8192 * 5120 * 16
    assert nbytes == 8192 * (2 * (2 * 5120 + 32) + 4 * 5120)
    # the bytes bind: a recurrence on the VPU is not the MXU's work
    assert peaks.least_time(ops, nbytes, "TPU v5 lite")[1] == "memory"
    back, _ = sambay_cost.selective_scan_cost(1, 8192, 5120, 16, True)
    assert back == 14 * 8192 * 5120 * 16


def test_the_readers_leave_the_line_on_a_program_without_the_scopes():
    records = {"trace": {}, "counters": {}, "config": published(),
               "device": {"kind": "TPU v5 lite"}}
    for name in ("sambay_scan_busy_pct", "sambay_scan_roofline",
                 "sambay_s6_proj_busy_pct", "sambay_gmu_busy_pct",
                 "sambay_cross_attn_busy_pct", "sambay_diff_combine_busy_pct",
                 "sambay_window_busy_pct", "sambay_window_roofline",
                 "sambay_window_blocks_visited_pct"):
        assert harness.metric_reader(name)(records) is None, name


def test_scope_of_takes_the_innermost_of_its_names():
    name = ("jit(train_grad)/jit(main)/layer_scan/transpose(jvp("
            "cross_attention))/pallas_flash_attention/foo")
    assert sambay_trace.scope_of(name) == "cross_attention"
    assert sambay_trace.scope_of(
        "jit(f)/layer_scan/attention/diff_attn_combine/mul") == (
            "diff_attn_combine")
    assert sambay_trace.scope_of("jit(f)/layer_scan/mlp/dot") is None
