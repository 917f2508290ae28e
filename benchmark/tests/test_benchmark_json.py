"""BENCHMARK.json against the contract's limits, and every name it holds
against a file: a configuration, a traffic mix, a driver or a per-layer
metric is added by adding a file and an entry, never by editing one."""

import json
import os
import re

from benchmark import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj|head_dim|"
                   r"_dim$|_rank$|expansion|per_tok)")


def test_exact_keys_and_limits():
    b = harness.load_benchmark()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["command"] == ["python3", "benchmark/run.py"]
    assert b["paths"] == ["benchmark"]
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    assert len(json.dumps(b)) < 64 * 1024
    n4 = sum(w["chips"] == 4 for w in b["workloads"])
    assert n4 <= max(1, len(b["workloads"]) // 4)
    # a full check of 24 cells at this length fits the driver's 43200 s
    assert (2 + 14 * 24) * (b["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_entries():
    b = harness.load_benchmark()
    cells = [w["name"] for w in b["workloads"]]
    cfgs = [c["name"] for c in b["configs"]]
    metrics = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    for names in (cells, cfgs, metrics):
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    assert len({(w["config"], w["traffic"]) for w in b["workloads"]}) == len(
        cells)
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in cfgs and w["chips"] in (1, 4)
        assert NAME.match(w["traffic"]) and 1 <= len(w["why"]) <= 200
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmark/") and len(c["why"]) <= 200
        assert not any(WIDTH.search(k) for k in c["reduced"])
        assert any(w["config"] == c["name"] for w in b["workloads"])
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    layers = set()
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        layers.add(m["layer"])
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
    # every cell: setup_s, another end-to-end metric, a per-layer metric
    # that moves a metric the cell reports
    for cell in cells:
        here = [m["name"] for m in b["end_to_end"]
                if cell in m.get("workloads", cells)]
        assert "setup_s" in here and len(here) >= 2
        assert any(cell in m.get("workloads", cells) and m["moves"] in here
                   for m in b["per_layer"])
        for m in b["per_layer"]:
            if cell in m.get("workloads", cells):
                assert m["moves"] in here, (cell, m["name"])


def test_every_name_resolves_to_a_file():
    b = harness.load_benchmark()
    for w in b["workloads"]:
        r = harness.resolve_cell(w["name"], b)
        assert os.path.isfile(r["driver"])
        assert r["config"]["source"] == [
            c["source"] for c in b["configs"] if c["name"] == w["config"]][0]
        assert r["config"]["reduced"] == [
            c["reduced"] for c in b["configs"] if c["name"] == w["config"]][0]
    for m in b["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))
    # a reader that finds nothing to read returns nothing
    for m in b["per_layer"]:
        assert harness.metric_reader(m["name"])({}) is None


def test_files_under_paths_are_named_from_name_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for d, dirs, files in os.walk(harness.BENCH_DIR):
        dirs[:] = [x for x in dirs if x not in (".out", ".cache",
                                                "__pycache__",
                                                ".pytest_cache")]
        for f in files:
            rel = os.path.relpath(os.path.join(d, f), harness.ROOT)
            assert ok.match(rel), rel
