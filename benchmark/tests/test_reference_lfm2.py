"""``reference_lfm2`` against cases worked by hand at tiny sizes (the taps'
order and edge, the two gates, a causal attention that no later token
moves, the bias that chooses and does not weigh, the gates over all routed
experts and a share's part, the tied head), ``shortconv_cost``'s counts at
the published sizes, and the new readers on plain data."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness, peaks, shortconv_cost, shortconv_trace
from benchmark import reference_lfm2 as ref

CELL = "lfm2-24b-a2b.train-toolcall-16k"
NEW_METRICS = (
    "shortconv_busy_pct", "shortconv_roofline", "shortconv_proj_busy_pct",
    "shortconv_resets_per_row", "lfm2_attn_busy_pct", "lfm2_attn_roofline",
    "lfm2_experts_busy_pct", "lfm2_experts_roofline", "lfm2_route_busy_pct",
    "lfm2_dense_mlp_busy_pct", "lfm2_local_rows_pct")
KEYS = {"hidden_size": 4, "num_attention_heads": 2, "num_key_value_heads": 1,
        "num_experts": 4, "num_experts_per_tok": 2, "norm_eps": 1e-5,
        "norm_topk_prob": True, "routed_scaling_factor": 1,
        "rope_parameters": {"rope_theta": 1e6, "rope_type": "default"}}


def published():
    with open(os.path.join(harness.BENCH_DIR, "configs",
                           "lfm2-24b-a2b.json")) as f:
        return json.load(f)


def test_the_taps_by_hand():
    # one channel, z = 1, 2, 3, 4, taps (100, 10, 1): the LAST tap is on
    # the token itself; what lies before the document reads 0
    z = jnp.asarray([[1.0], [2.0], [3.0], [4.0]])
    w = jnp.asarray([[100.0], [10.0], [1.0]])
    np.testing.assert_allclose(ref.taps(z, w)[:, 0], [1, 12, 123, 234])
    np.testing.assert_allclose(
        ref.taps(z, w, frozenset({"taps_reversed"}))[:, 0],
        [100, 210, 321, 432])


def test_both_gates_and_no_activation():
    # D = 1: in_proj gives [B | C | x] = u * (2, 3, 5); taps (0, 0, 1)
    lp = {"sc_in": jnp.asarray([[2.0, 3.0, 5.0]]),
          "sc_conv": jnp.asarray([[0.0], [0.0], [1.0]]),
          "sc_out": jnp.asarray([[1.0]])}
    u = jnp.asarray([[1.0], [-1.0]])
    # y = C * (B * x) = 3u * (2u * 5u) = 30 u^3: negative stays negative
    np.testing.assert_allclose(ref.shortconv(u, KEYS, lp)[:, 0], [30, -30])
    got = {w: np.asarray(ref.shortconv(u, KEYS, lp, frozenset({w})))[:, 0]
           for w in ("no_b_gate", "no_c_gate", "silu_after_conv")}
    np.testing.assert_allclose(got["no_b_gate"], [15, 15])   # C * x
    np.testing.assert_allclose(got["no_c_gate"], [10, 10])   # B * x
    silu = 10 / (1 + np.exp(-10.0))
    np.testing.assert_allclose(got["silu_after_conv"], [3 * silu, -3 * silu],
                               rtol=1e-6)


def params_of(seed=0, E=4, held=4, d=4, f=6, fe=3, V=11):
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 32))

    def n(*shape, s=0.5):
        return s * jax.random.normal(next(ks), shape)

    base = {"ln1": 1 + n(1, d, s=0.1), "ln2": 1 + n(1, d, s=0.1)}
    conv = {"sc_in": n(1, d, 3 * d), "sc_conv": n(1, 3, d), "sc_out": n(1, d, d)}
    attn = {"wq": n(1, d, d), "wk": n(1, d, d // 2), "wv": n(1, d, d // 2),
            "wo": n(1, d, d), "q_norm": 1 + n(1, d // 2, s=0.1),
            "k_norm": 1 + n(1, d // 2, s=0.1)}
    dense = {"w_gate": n(1, d, f), "w_up": n(1, d, f), "w_down": n(1, f, d)}
    experts = {"router": n(1, d, E, s=1.0), "router_bias": n(1, E, s=0.3),
               "e_gate": n(1, held, d, fe), "e_up": n(1, held, d, fe),
               "e_down": n(1, held, fe, d)}
    return {"embedding": n(V, d, s=1.0), "final_ln": 1 + n(d, s=0.1),
            "layers": {"conv_dense": {**base, **conv, **dense},
                       "full": {**base, **attn, **experts},
                       "conv": {**base, **conv, **experts}}}


MODEL = {**KEYS, "num_hidden_layers": 3, "num_dense_layers": 1,
         "layer_types": ["conv", "full_attention", "conv"], "vocab_size": 11}


def test_no_later_token_moves_an_earlier_logit_and_the_head_is_tied():
    p = params_of()
    tok = jnp.asarray([3, 7, 2, 9, 5, 4])
    a = ref.logits(p, MODEL, tok)
    b = ref.logits(p, MODEL, tok.at[4:].set(jnp.asarray([8, 10])))
    np.testing.assert_allclose(a[:4], b[:4], atol=1e-6)
    assert np.abs(a[4:] - b[4:]).max() > 1e-3
    h = ref.rms(ref.hidden(p, MODEL, tok), p["final_ln"], 1e-5)
    np.testing.assert_allclose(a, h @ p["embedding"].T, atol=1e-5)
    untied = {**p, "lm_head": 2.0 * p["embedding"].T}
    np.testing.assert_allclose(ref.logits(untied, MODEL, tok), 2 * a,
                               atol=1e-5)
    lp = ref.token_logprobs(p, MODEL, tok)
    want = jax.nn.log_softmax(a[:-1], -1)[jnp.arange(5), tok[1:]]
    np.testing.assert_allclose(lp, want, atol=1e-6)


def test_the_bias_chooses_and_the_scores_weigh():
    lp = {"router": jnp.eye(4) * 1.0,
          "router_bias": jnp.asarray([0.0, 0.0, 0.0, 5.0])}
    x = jnp.asarray([[2.0, 1.0, 0.0, -3.0]])
    s = np.asarray(jax.nn.sigmoid(x[0]))
    g = ref.gates(x, KEYS, lp)[0]
    # chosen: expert 3 (by its bias) and expert 0; weighed by SCORES
    want = np.zeros(4)
    want[[0, 3]] = s[[0, 3]] / (s[0] + s[3] + 1e-6)
    np.testing.assert_allclose(g, want, rtol=1e-6)
    out = ref.gates(x, KEYS, lp, frozenset({"bias_left_out_of_choice"}))[0]
    assert set(np.nonzero(np.asarray(out))[0]) == {0, 1}
    added = ref.gates(x, KEYS, lp, frozenset({"bias_added_to_gates"}))[0]
    assert added[3] > 0.8  # (s3 + 5) dominates
    raw = ref.gates(x, KEYS, lp, frozenset({"gates_not_renormalised"}))[0]
    np.testing.assert_allclose(np.asarray(raw)[[0, 3]], s[[0, 3]], rtol=1e-6)
    soft = ref.gates(x, KEYS, lp, frozenset({"softmax_for_sigmoid"}))[0]
    assert abs(float(soft[3]) - float(g[3])) > 1e-3


def test_the_shares_parts_add_up():
    p = params_of(1)
    lp = {k: w[0] for k, w in p["layers"]["conv"].items()}
    x = jax.random.normal(jax.random.PRNGKey(2), (7, 4))
    whole = ref.moe(x, KEYS, lp)
    parts = 0
    for shard in range(2):
        keys = {**KEYS, "num_experts": 2, "num_routed_experts": 4,
                "expert_shard_index": shard}
        held = {**lp, **{k: lp[k][2 * shard:2 * shard + 2]
                         for k in ("e_gate", "e_up", "e_down")}}
        parts = parts + ref.moe(x, keys, held)
    np.testing.assert_allclose(parts, whole, atol=1e-5)


@pytest.mark.parametrize("which", ref.WRONG)
def test_every_wrong_model_differs(which):
    p = params_of(3)
    tok = jnp.asarray([3, 7, 2, 9, 5, 4, 6, 8])
    a = ref.token_logprobs(p, MODEL, tok)
    b = ref.token_logprobs(p, MODEL, tok, frozenset({which}))
    assert float(jnp.abs(a - b).max()) > 1e-5, which


def test_the_ppo_loss_leaves_masked_tokens_alone():
    p = params_of(4)
    tok = jnp.asarray([3, 7, 2, 9, 5, 4])
    old = ref.token_logprobs(p, MODEL, tok)
    adv = jnp.asarray([1.0, -1.0, 2.0, 0.5, -0.5])
    mask = jnp.asarray([0.0, 0.0, 1.0, 1.0, 1.0])
    # at ratio 1 the surrogate is the masked mean advantage
    np.testing.assert_allclose(
        ref.ppo_loss(p, MODEL, tok, old, adv, mask), -2.0 / 3, rtol=1e-5)
    moved = ref.ppo_loss(p, MODEL, tok, old.at[:2].add(3.0), adv, mask)
    np.testing.assert_allclose(moved, -2.0 / 3, rtol=1e-5)


# ---- the counts, at the published sizes ----

def test_the_counts_at_the_published_sizes():
    cfg = published()
    assert shortconv_cost.layer_counts(cfg) == {
        "conv": 4, "full": 1, "dense": 1, "experts": 4}
    assert shortconv_cost.conv_runs(cfg) == 2  # c(dense) | A | c c c
    # 16 KB a token a block forward, 28 KB backward, in bfloat16
    ops, nbytes = shortconv_cost.glue_cost(1, 16384, 2048, 3, False)
    assert nbytes == 16384 * 16 * 1024 and ops == 16384 * 2048 * 7
    ops_b, bytes_b = shortconv_cost.glue_cost(1, 16384, 2048, 3, True)
    assert bytes_b == 16384 * 28 * 1024 and ops_b > 2 * ops
    # bandwidth binds it on a v5e
    assert peaks.least_time(ops, nbytes, "TPU v5e")[1] == "memory"
    # attention a DOCUMENT at a time: two halves are half of one whole
    one, _ = shortconv_cost.attention_cost(cfg, [8192], False)
    two, _ = shortconv_cost.attention_cost(cfg, [4096, 4096], False)
    assert two == pytest.approx(one / 2)
    assert one == peaks.flash_attention_cost(1, 8192, 32, 8, 64, False)[0]
    # 186.1 M: 4 x 16.8 M of conv projections, 10.5 M of attention, the
    # dense FFN's 72.4 M, 4 x (router + half an expert), the head 16.8 M
    assert shortconv_cost.share_params(cfg) == 186_122_240
    assert shortconv_cost.projection_cost(1, 2048) == 2 * 4 * 2048 ** 2


def test_the_new_metrics_are_files_and_entries_of_the_new_cell_only():
    bench = harness.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL], name
        assert by_name[name]["moves"] == "train_tok_s_chip"
        assert callable(harness.metric_reader(name))
    r = harness.resolve_cell(CELL)
    assert r["traffic"]["driver"] == "train_lfm2"
    assert r["config"]["reference"] == "reference_lfm2"
    reported = {m["name"] for m in r["per_layer"]}
    assert set(NEW_METRICS) <= reported
    assert {"train_mfu_pct", "setup_compile_s", "pack_fill_pct"} <= reported


def test_the_readers_on_plain_records_and_on_a_program_without_the_block():
    # no trace, no counters (the parent commit): every reader gives None
    for name in NEW_METRICS:
        assert harness.metric_reader(name)({"counters": {}}) is None, name
    rec = {"counters": {"shortconv_resets_per_row": 4.5,
                        "moe_routed_rows": 800.0, "moe_local_rows": 100.0}}
    assert shortconv_trace.resets_per_row(rec) == 4.5
    assert harness.metric_reader("lfm2_local_rows_pct")(rec) == 12.5


def test_the_cells_bias_is_drawn_small_and_not_zero():
    """The configuration's ``expert_bias_init_std`` reaches the program's
    init: ``expert_bias`` is drawn at it (not at the matrices' 0.02, not
    zero) and every other leaf of the expert layer as before."""
    from areal_tpu.models import moe
    from benchmark import weights

    cfg_file = published()
    cfg = weights.model_config(cfg_file)
    assert cfg.moe.router_bias_init_std == cfg_file["expert_bias_init_std"]
    small = dataclasses.replace(
        cfg, hidden_dim=16, moe=dataclasses.replace(
            cfg.moe, routed_intermediate_dim=8))
    p = moe.init_moe_params(small, jax.random.PRNGKey(3), jnp.float32, n=3)
    bias = np.asarray(p["router_bias"])
    assert bias.shape == (3, cfg_file["num_routed_experts"])
    assert 0.7 < bias.std() / cfg_file["expert_bias_init_std"] < 1.3
    assert 0.9 < np.asarray(p["router"]).std() / 0.02 < 1.1
