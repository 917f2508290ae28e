"""The train window's reduction (``readers.window_throughput``) on known
steps: one stalled step moves the mean rate and ``slow_step_s`` and not
the end-to-end rate, a slowdown that recurs moves both, and traced steps
are left out of all three."""

import pytest

from benchmark import readers


def steps(secs, traced=()):
    return [{"batch": i % 2, "secs": s, "traced": i in traced}
            for i, s in enumerate(secs)]


def test_calm_window_all_rates_agree():
    for tail in ([], [1.0]):  # a window may end between two batches
        r = readers.window_throughput(steps([1.0, 2.0] * 4 + tail),
                                      [100, 300])
        assert r["tok_s"] == pytest.approx(400 / 3.0)
        assert r["mean_tok_s"] == pytest.approx(400 / 3.0)
        assert r["slow_step_s"] == 0.0


def test_one_stalled_step_moves_mean_and_slow_step_only():
    r = readers.window_throughput(steps([1, 2, 1, 5, 1, 2, 1, 2, 1]),
                                  [100, 300])
    assert r["tok_s"] == pytest.approx(400 / 3.0)
    assert r["mean_tok_s"] == pytest.approx(400 / (1.0 + 11 / 4))
    assert r["slow_step_s"] == pytest.approx(3.0)


def test_a_stall_every_third_step_moves_the_end_to_end_rate():
    secs = [1.0, 2.0] * 6
    for i in range(0, 12, 3):
        secs[i] += 1.0  # batch 0 at visits 0 and 3, batch 1 at 1 and 4
    r = readers.window_throughput(steps(secs), [100, 300])
    assert r["tok_s"] == pytest.approx(400 / (1.2 + 2.2))
    assert r["mean_tok_s"] == pytest.approx(400 / (3.0 + 4 / 6))


def test_traced_steps_are_left_out():
    r = readers.window_throughput(
        steps([1, 2, 9, 2, 1, 2, 1], traced={2, 3}), [100, 300])
    assert r["tok_s"] == r["mean_tok_s"] == pytest.approx(400 / 3.0)
    assert readers.window_throughput(steps([1], traced={0}), [100, 300])[
        "tok_s"] is None
