import json
import os

import numpy as np
import pytest

from benchmark import harness, traffic

TRAFFIC_DIR = os.path.join(harness.BENCH_DIR, "traffic")
FILES = sorted(f for f in os.listdir(TRAFFIC_DIR) if f.endswith(".json"))


def load(name):
    with open(os.path.join(TRAFFIC_DIR, name)) as f:
        return json.load(f)


SHAPED = [f for f in FILES if "shape" in load(f)]


@pytest.mark.parametrize("name", SHAPED)
def test_generator_is_a_pure_function_of_the_seed(name):
    shape = load(name)["shape"]
    a = traffic.make_groups(shape, 40, seed=7, vocab_size=1000)
    b = traffic.make_groups(shape, 40, seed=7, vocab_size=1000)
    c = traffic.make_groups(shape, 40, seed=8, vocab_size=1000)
    for x, y in zip(a, b):
        assert x.new_tokens == y.new_tokens
        np.testing.assert_array_equal(x.prompt_ids, y.prompt_ids)
    # another seed: other contents, the same lengths (shapes come from the
    # traffic file, so that no run compiles what an earlier run has not)
    assert any((x.prompt_ids != z.prompt_ids).any() for x, z in zip(a, c))
    assert [len(x.prompt_ids) for x in a] == [len(z.prompt_ids) for z in c]
    assert [x.new_tokens for x in a] == [z.new_tokens for z in c]
    ids = np.concatenate([x.prompt_ids for x in a])
    assert ids.min() >= shape["reserved_ids"] and ids.max() < 1000


@pytest.mark.parametrize("name", SHAPED)
def test_length_histograms_match_the_stated_distributions(name):
    shape = dict(load(name)["shape"])
    lens = traffic.draw_lengths(shape, 20000)
    from math import erf, log, sqrt

    def cdf(x, p):  # of the unclipped lognormal
        return 0.5 * (1 + erf((log(x) - log(p["median"]))
                              / (p["sigma"] * sqrt(2))))

    for key in ("prompt_len", "new_tokens"):
        p, x = shape[key], lens[key]
        m = p.get("multiple_of", 1)
        assert x.min() >= p["min"] and x.max() <= p["max"]
        assert not (x % m).any()
        # the mass the clip gathers at each end
        assert abs(np.mean(x == p["min"]) - cdf(p["min"] + m / 2, p)) < 0.02
        assert abs(np.mean(x == p["max"])
                   - (1 - cdf(p["max"] - m / 2, p))) < 0.02
        if m > 1:  # whole chunks: the ends hold most of it
            continue
        assert abs(np.median(x) - p["median"]) / p["median"] < 0.03
        for q in (0.3, 0.7):
            v = np.quantile(x, q)
            if p["min"] < v < p["max"]:
                assert abs(cdf(v, p) - q) < 0.02


def test_train_batches_share_prompt_and_budget_inside_a_group():
    t = load("train-packed.json")
    bs = traffic.make_train_batches(t["shape"], 2, 4, 16, seed=3,
                                    vocab_size=5000)
    again = traffic.make_train_batches(t["shape"], 2, 4, 16, seed=3,
                                       vocab_size=5000)
    for b, b2 in zip(bs, again):
        np.testing.assert_array_equal(b["packed_input_ids"],
                                      b2["packed_input_ids"])
        assert len(b["seqlens"]) == 64 and len(set(b["group"])) == 4
        assert int(b["seqlens"].sum()) == len(b["packed_input_ids"]) == len(
            b["prompt_mask"])
        for g in set(b["group"]):
            idx = [i for i, x in enumerate(b["group"]) if x == g]
            assert len({int(b["seqlens"][i]) for i in idx}) == 1
        assert set(np.unique(b["rewards"])) <= {-1.0, 1.0}
