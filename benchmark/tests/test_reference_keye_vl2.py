"""The Keye-VL-2.0 cell's benchmark files hold together: the cost
functions count what the program counts, the configuration file is the
catalog's row with its three cuts, BENCHMARK.json names the cell and its
three metrics, and the driver's call counting follows the remat plan."""

import json
import os

import pytest

from benchmark import dsa_cost, harness

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "keye-vl-2.0-30b-a3b.train-video-reason-16k"


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "keye-vl-2.0-30b-a3b.json")) as f:
        return json.load(f)


def test_pairs_are_what_the_program_counts():
    from areal_tpu.models import dsa

    docs = (9919, 7808, 2048, 2049, 1, 300)
    assert dsa_cost.selected_pairs(docs, 2048) == dsa.host_selected_pairs(
        docs, 2048)
    assert dsa_cost.causal_pairs(docs) == dsa.host_causal_pairs(docs)
    # below the top-k every causal pair is selected
    assert dsa_cost.selected_pairs((300, 2048), 2048) == \
        dsa_cost.causal_pairs((300, 2048))
    # the traffic file's two shares (its ``what``)
    for n, share in ((9919, 0.37), (7808, 0.46)):
        got = dsa_cost.selected_pairs((n,), 2048) / dsa_cost.causal_pairs((n,))
        assert abs(got - share) < 0.005


def test_the_configuration_is_the_catalogs_row_with_three_cuts(cfg):
    rows = [json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")] \
        if os.path.exists(
            "/opt/skills/guides/model-configs/architectures.jsonl") else []
    row = next((r for r in rows if r["name"] == "Keye-VL-2.0-30B-A3B"), None)
    if row is not None:
        assert cfg["source"] == row["source_url"]
        differs = sorted(k for k, v in row["config"].items()
                         if cfg.get(k) != v)
        assert differs == sorted(cfg["reduced"])
        assert {k: row["config"][k] for k in cfg["reduced"]} == \
            cfg["reduced_from"]
    assert cfg["n_parameters"] == dsa_cost.share_params(cfg) == 432_697_600
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"]) == (2048, 128, 32, 4, 768, 8)
    assert cfg["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
        "q_chunk_size": 512, "topk": 2048}


def test_the_benchmark_declares_the_cell_and_its_metrics():
    r = harness.resolve_cell(CELL)
    assert r["cell"]["chips"] == 1 and r["config_name"] == "keye-vl-2.0-30b-a3b"
    assert os.path.basename(r["driver"]) == "train_keye_vl2.py"
    own = [m["name"] for m in r["per_layer"] if m.get("workloads") == [CELL]]
    assert own == ["dsa_attn_roofline", "dsa_attn_busy_pct",
                   "dsa_select_busy_pct"]
    for name in own:
        assert callable(harness.metric_reader(name))
        assert harness.metric_reader(name)({}) is None  # nothing to read
    bench = harness.load_benchmark()
    for other in bench["workloads"]:
        if other["name"] != CELL:
            names = {m["name"] for m in
                     harness.resolve_cell(other["name"])["per_layer"]}
            assert not {n for n in names if n.startswith(("dsa_", "keye_"))}


def test_costs_scale_with_the_selected_pairs(cfg):
    short, long_ = (2048,), (16384,)
    ops_s, _ = dsa_cost.attention_cost(cfg, short, False)
    ops_l, _ = dsa_cost.attention_cost(cfg, long_, False)
    assert ops_s == 4 * 32 * 128 * dsa_cost.causal_pairs(short)
    assert ops_l == 4 * 32 * 128 * dsa_cost.selected_pairs(long_, 2048)
    assert dsa_cost.attention_cost(cfg, long_, True)[0] == 2.5 * ops_l
    # a full-causal sweep of 16k does 4.3 x the selected work
    assert 4.2 < dsa_cost.causal_pairs(long_) / dsa_cost.selected_pairs(
        long_, 2048) < 4.3
    ops_i, _ = dsa_cost.index_cost(cfg, long_)
    assert ops_i == (2 * 16384 * 2048 * (1024 + 64 + 16)
                     + 2 * 16 * 64 * dsa_cost.causal_pairs(long_))
    assert dsa_cost.select_cost(cfg, long_) == (
        0.0, 4.0 * dsa_cost.causal_pairs(long_))


def test_the_drivers_calls_follow_the_remat_plan(cfg):
    from benchmark.drivers import train_keye_vl2 as drv

    layouts = [("infer", "1x9984", (9919,)), ("train", "1x9984", (9919,)),
               ("train", "2x7808", (7808, 7808))]
    plan = {"1x9984": {"entry": "attention"}, "2x7808": {"entry": "full"}}
    calls = {c["grid"]: c for c in drv.kernel_calls(cfg, layouts, plan)}
    n = cfg["num_hidden_layers"]
    assert calls["1x9984"] == {"grid": "1x9984", "documents": [9919],
                               "fwd": 2 * n, "bwd": n, "scorings": 2 * n}
    assert calls["2x7808"]["fwd"] == 2 * n  # the forward ``full`` re-runs
    assert calls["2x7808"]["scorings"] == n  # the selection never re-runs
