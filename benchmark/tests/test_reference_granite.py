"""``reference_granite_hybrid`` against cases worked by hand at tiny sizes
(the recurrence's closed form under a constant decay, the convolution's
edge, the gate before the norm and the norm over ALL channels of one
group, the softmax scale in place of 1/sqrt(head), the four multipliers
on a model whose layers are the identity), ``granite_cost``'s counts at
the published sizes, and the new readers on plain data."""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import granite_cost, granite_trace, harness, peaks, ssm_cost
from benchmark import reference_granite_hybrid as ref

CELL = "granite-4.0-h-micro.train-rag-packed"


def published():
    with open(os.path.join(harness.BENCH_DIR, "configs",
                           "granite-4.0-h-micro.json")) as f:
        return json.load(f)


def test_the_recurrence_is_a_decayed_sum():
    # one head of one channel, one state, Δ = 1, A = -ln 2:
    # S_t = S_{t-1} / 2 + x_t, y_t = 2 S_t
    T = 6
    x = jnp.arange(1.0, T + 1)[:, None, None]
    y = ref.scan(x, jnp.ones((T, 1)), jnp.full((1,), -math.log(2.0)),
                 jnp.ones((T, 1, 1)), 2.0 * jnp.ones((T, 1, 1)))
    want = [2 * sum(2.0 ** -(t - s) * (s + 1) for s in range(t + 1))
            for t in range(T)]
    np.testing.assert_allclose(y[:, 0, 0], want, rtol=1e-6)


def test_the_convolution_reads_zero_before_the_document():
    x = jnp.arange(1.0, 5.0)[:, None]
    w = jnp.asarray([[1.0], [10.0], [100.0], [1000.0]])  # w[3] on the token
    got = ref.conv(x, w, jnp.asarray([0.5]))[:, 0]
    np.testing.assert_allclose(got, [1000.5, 2100.5, 3210.5, 4321.5])


def test_the_gate_comes_first_and_one_group_spans_every_channel():
    y = jnp.asarray([[3.0, 4.0, 0.0, 0.0]])
    z = jnp.zeros((1, 4))  # silu(0) = 0: the gate first gives 0 / sqrt(eps)
    assert float(jnp.abs(ref.gated_norm(y, z, jnp.ones(4), 1, 1e-5)).max()
                 ) == 0.0
    z = jnp.full((1, 4), 50.0)  # silu(50) = 50
    got = ref.gated_norm(y, z, jnp.ones(4), 1, 0.0)
    # RMS over all 4 channels of [150, 200, 0, 0] is 125
    np.testing.assert_allclose(got, [[1.2, 1.6, 0, 0]], rtol=1e-5)
    two = ref.gated_norm(y, z, jnp.ones(4), 2, 0.0)
    np.testing.assert_allclose(two[0, :2], np.asarray([150, 200]) / math.sqrt(
        (150 ** 2 + 200 ** 2) / 2), rtol=1e-5)
    # a share's statistic taken over the deployment's channels
    shared = ref.gated_norm(y[:, :2], z[:, :2], jnp.ones(2), 1, 0.0,
                            sum_sq=jnp.asarray([[150.0 ** 2 + 200 ** 2]]),
                            width=4)
    np.testing.assert_allclose(shared, got[:, :2], rtol=1e-5)


def test_the_softmax_scale_is_the_multiplier_not_the_heads():
    cfg = {"num_attention_heads": 1, "num_key_value_heads": 1, "head_dim": 2,
           "hidden_size": 2, "attention_multiplier": 0.5}
    eye = jnp.eye(2)
    lp = {"wq": eye, "wk": eye, "wv": eye, "wo": eye}
    u = jnp.asarray([[1.0, 0.0], [2.0, 0.0]])
    got = ref.attention(u, cfg, lp)
    # token 1: scores 0.5 * [2, 4] -> softmax [1, e] / (1 + e)
    p = math.e / (1 + math.e)
    np.testing.assert_allclose(got[1], [(1 - p) * 1 + p * 2, 0], rtol=1e-6)
    np.testing.assert_allclose(got[0], [1.0, 0.0], rtol=1e-6)
    by_head = ref.attention(u, {**cfg, "attention_multiplier": None}, lp)
    p = math.exp(4 / math.sqrt(2)) / (math.exp(2 / math.sqrt(2))
                                      + math.exp(4 / math.sqrt(2)))
    np.testing.assert_allclose(by_head[1, 0], (1 - p) + 2 * p, rtol=1e-6)


def test_the_four_multipliers_on_a_model_of_identity_blocks():
    """Every branch zero (W_o = 0, out_proj = 0): h_L = 12 E[ids], logits
    = rms(12 E[ids]) E^T / 8."""
    cfg = {"num_hidden_layers": 1, "layer_types": ["mamba"],
           "hidden_size": 4, "mamba_n_heads": 1, "mamba_d_head": 4,
           "mamba_n_groups": 1, "mamba_d_state": 2, "rms_norm_eps": 0.0,
           "embedding_multiplier": 12, "residual_multiplier": 0.22,
           "logits_scaling": 8, "tie_word_embeddings": True}
    E = jnp.asarray([[1.0, 0, 0, 0], [0, 2.0, 0, 0], [0, 0, 3.0, 0]])
    ssd = {"ln1": jnp.ones((1, 4)), "ln2": jnp.ones((1, 4)),
           "in_proj": jnp.ones((1, 4, 4 + 4 + 4 + 1)),
           "conv_w": jnp.ones((1, 4, 8)), "conv_b": jnp.zeros((1, 8)),
           "dt_bias": jnp.zeros((1, 1)), "A_log": jnp.zeros((1, 1)),
           "D": jnp.ones((1, 1)), "norm": jnp.ones((1, 4)),
           "out_proj": jnp.zeros((1, 4, 4)), "w_gate": jnp.ones((1, 4, 6)),
           "w_up": jnp.ones((1, 4, 6)), "w_down": jnp.zeros((1, 6, 4))}
    params = {"embedding": E, "final_ln": jnp.ones(4),
              "layers": {"ssd": ssd}}
    tok = jnp.asarray([0, 1, 2, 1])
    np.testing.assert_allclose(ref.hidden(params, cfg, tok), 12 * E[tok])
    # rms(12 e_i a) = 2 e_i (4 channels, one set): logit_ij = 2 E_jj / 8
    want = 2 * jnp.eye(3)[tok] * jnp.diag(E[:, :3]) / 8
    np.testing.assert_allclose(ref.logits(params, cfg, tok), want, rtol=1e-6)
    # the residual multiplier scales BOTH branches
    live = {**params, "layers": {"ssd": {
        **ssd, "w_down": jnp.ones((1, 6, 4)), "out_proj": jnp.ones((1, 4, 4))}}}
    one = ref.hidden(live, {**cfg, "residual_multiplier": 1.0}, tok)
    u = ref._rms(12 * E[tok], 1.0, 0.0)
    lp = {k: w[0] for k, w in live["layers"]["ssd"].items()}
    mix = ref.mamba(u, cfg, lp)
    h1 = 12 * E[tok] + mix
    np.testing.assert_allclose(
        one, h1 + ref.mlp(ref._rms(h1, 1.0, 0.0), lp), rtol=1e-5)
    h1 = 12 * E[tok] + 0.22 * mix
    np.testing.assert_allclose(
        ref.hidden(live, cfg, tok),
        h1 + 0.22 * ref.mlp(ref._rms(h1, 1.0, 0.0), lp), rtol=1e-5)


def test_loss_is_the_mean_negative_logprob_and_has_a_gradient():
    cfg = {"num_hidden_layers": 0, "layer_types": [], "hidden_size": 2,
           "rms_norm_eps": 1e-5, "embedding_multiplier": 12,
           "logits_scaling": 8, "tie_word_embeddings": True}
    params = {"embedding": jnp.asarray([[1.0, 0.5], [0.2, -1.0], [0.3, 0.3]]),
              "final_ln": jnp.ones(2), "layers": {}}
    tok = jnp.asarray([0, 2, 1])
    lp = ref.token_logprobs(params, cfg, tok)
    assert lp.shape == (2,) and float(lp.max()) < 0
    np.testing.assert_allclose(ref.loss(params, cfg, tok), -lp.mean())
    np.testing.assert_allclose(ref.loss(params, cfg, tok, [1.0, 0.0]), -lp[0])
    g = jax.grad(lambda p: ref.loss(p, cfg, tok))(params)
    assert float(jnp.abs(g["embedding"]).max()) > 0


def test_the_pattern_and_the_counts_at_the_published_sizes():
    cfg = published()
    assert granite_cost.layer_counts(cfg) == {"mamba": 9, "attention": 1}
    whole = {**cfg, **cfg["reduced_from"], "first_layer_index": 0}
    assert granite_cost.layer_counts(whole) == {"mamba": 36, "attention": 4}
    # the cut starts at the period's attention layer: one run of Mamba
    # blocks, where the published stack has five (5, 9, 9, 9, 4)
    assert cfg["first_layer_index"] == 5
    assert ref.layer_types(cfg) == ["attention"] + ["mamba"] * 9 == (
        granite_cost.layer_types(cfg))
    assert granite_cost.mamba_runs(cfg) == 1
    assert granite_cost.mamba_runs(whole) == 5
    assert [k for k, _ in ref.layers_of(
        {"layers": {"ssd": {}, "full": {}}}, cfg)] == (
            ["full"] + ["ssd"] * 9)
    # the file's arithmetic: 652.97 M with norms, the convolution and the
    # scan's vectors; the matrices alone are 0.17 M fewer; 6 N = 3.92 GFLOP
    assert round(granite_cost.share_params(cfg) / 1e6, 1) == 652.8
    assert round(6 * granite_cost.share_params(cfg) / 1e9, 2) == 3.92
    assert ref.attention_head_dim(cfg) == 64
    assert ref.attention_head_dim(whole) == 64 == 2048 // 32
    # one scan of a row of 8192 at chunk 256: the matmuls bind, not bytes
    ops, nbytes = ssm_cost.ssd_scan_cost(1, 8192, 256, 32, 64, 1, 128, False)
    chunks = 8192 // 256
    assert ops == chunks * (2 * 256 * 256 * 128 + 32 * (
        2 * 256 * 256 * 64 + 4 * 256 * 64 * 128 + 2 * 64 * 128))
    assert peaks.least_time(ops, nbytes, "TPU v5 lite")[1] == "compute"
    # 1.6 % of the model's FLOPs, as the issue reckons
    assert 0.01 < 9 * ops / (2 * granite_cost.share_params(cfg) * 8192) < 0.03


def test_the_benchmark_declares_the_cell_and_its_five_metrics():
    bench = harness.load_benchmark()
    r = harness.resolve_cell(CELL, bench)
    assert r["cell"]["chips"] == 1 and r["traffic"]["driver"] == "train_granite"
    assert r["config"]["reference"] == "reference_granite_hybrid"
    mine = {m["name"]: m for m in bench["per_layer"]
            if m["name"].startswith("granite_")}
    assert sorted(mine) == ["granite_docs_per_row", "granite_scan_busy_pct",
                            "granite_scan_roofline",
                            "granite_ssm_glue_busy_pct",
                            "granite_ssm_proj_busy_pct"]
    assert all(m["workloads"] == [CELL] and m["moves"] == "train_tok_s_chip"
               for m in mine.values())
    # no metric asks this cell for what nothing here reports
    names = {m["name"] for m in r["per_layer"]}
    assert not {"flash_attn_busy_pct", "flash_attn_roofline"} & names
    assert not any(n.startswith(("ssm_", "sambay_", "latent_")) for n in names)
    t = r["traffic"]
    assert t["n_batches"] == 3 and t["shape"]["prompt_len"] == {
        "dist": "lognormal", "median": 1536, "sigma": 0.5, "min": 512,
        "max": 3072}
    assert t["shape"]["new_tokens"] == {
        "dist": "lognormal", "median": 512, "sigma": 0.7, "min": 128,
        "max": 2048}


def test_the_readers_leave_the_line_on_a_program_without_the_scopes():
    records = {"trace": {}, "counters": {}, "config": published(),
               "device": {"kind": "TPU v5 lite"}}
    for name in ("granite_scan_busy_pct", "granite_scan_roofline",
                 "granite_ssm_proj_busy_pct", "granite_ssm_glue_busy_pct",
                 "granite_docs_per_row"):
        assert harness.metric_reader(name)(records) is None, name
    records["counters"]["docs_per_row"] = 3.2
    assert harness.metric_reader("granite_docs_per_row")(records) == 3.2


def test_the_roofline_is_the_least_time_over_the_scopes_time(monkeypatch):
    from benchmark import ssm_trace

    call = {"rows": 1, "length": 8192, "chunk": 256, "heads": 32,
            "head_dim": 64, "groups": 1, "state": 128, "fwd": 3, "bwd": 1}
    records = {"trace": {"x": 1}, "device": {"kind": "TPU v5 lite"},
               "counters": {"granite_scan_calls_traced": [call]}}
    monkeypatch.setattr(ssm_trace, "scope_seconds", lambda r, *s: 0.01)
    f_ops, f_b = ssm_cost.ssd_scan_cost(1, 8192, 256, 32, 64, 1, 128, False)
    b_ops, b_b = ssm_cost.ssd_scan_cost(1, 8192, 256, 32, 64, 1, 128, True)
    least = (3 * peaks.least_time(f_ops, f_b, "TPU v5 lite")[0]
             + peaks.least_time(b_ops, b_b, "TPU v5 lite")[0])
    assert granite_trace.scan_roofline(records) == pytest.approx(
        100 * least / 0.01)
