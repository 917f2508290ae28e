"""Math-grader parity corpus (docs/rewards.md §Parity corpus).

~200 (generated answer, ground-truth solutions, expected verdict) fixture
pairs spanning the reference grader's semantic surface — integers,
fractions/decimals, percent scaling, mixed numbers, scientific notation,
sqrt/pi symbolics, units/LaTeX noise, multiple choice, tuples, intervals,
matrices, equations, extraction rules, tolerance — checked into
tests/fixtures/math_parity_corpus.jsonl.

Entries carrying a ``divergence`` field are the documented allowlist of
KNOWN deviations from the reference grader (each records the reference's
verdict in ``reference_expected`` and why ours differs); everything else
must agree exactly. The allowlist is pinned by id here so a new
divergence cannot slip in silently.
"""

import functools
import json
import os

import pytest

from areal_tpu.rewards import math_verify
from areal_tpu.rewards.client import batch_reward
from areal_tpu.rewards.math_verify import verify_math

pytestmark = pytest.mark.rewards

CORPUS = os.path.join(os.path.dirname(__file__), "fixtures",
                      "math_parity_corpus.jsonl")

# The documented allowlist (docs/rewards.md): bracket-type-sensitive
# intervals (two entries) and the 192-char symbolic comparison cap.
KNOWN_DIVERGENCES = {"p082", "p083", "p116"}


def _corpus():
    with open(CORPUS) as f:
        return [json.loads(ln) for ln in f if ln.strip()]


def test_corpus_shape_and_allowlist_pinned():
    entries = _corpus()
    assert len(entries) >= 200
    assert len({e["id"] for e in entries}) == len(entries)
    flagged = {e["id"] for e in entries if "divergence" in e}
    assert flagged == KNOWN_DIVERGENCES, (
        "divergence allowlist drifted — document any new deviation in the "
        "fixture AND docs/rewards.md, then pin it here"
    )
    for e in entries:
        if "divergence" in e:
            # every allowlisted entry records the reference's verdict and
            # actually DIFFERS from ours (else it isn't a divergence)
            assert e["reference_expected"] != e["expected"], e["id"]


@pytest.fixture()
def patient_sympy(monkeypatch):
    """No entry of the corpus is MEANT to run out of the sympy child's
    time (the longest, the first, takes 1.6 s on an idle core), but under
    six loaded workers one did, and its verdict flipped (ROADMAP D12). A
    verdict is what these tests compare, so here the child gets a budget
    that load cannot exhaust; the grader's default of 3.0 s, which a
    reward worker runs with, is not touched."""
    monkeypatch.setattr(
        math_verify, "_symbolic_equal",
        functools.partial(math_verify._symbolic_equal, timeout=120.0))


def test_math_grader_agrees_on_whole_corpus(patient_sympy):
    mism = []
    for e in _corpus():
        got = verify_math(e["generated"], e["solutions"])
        if got != e["expected"]:
            mism.append((e["id"], e.get("note"), e["expected"], got))
    assert not mism, f"{len(mism)} corpus mismatches: {mism[:10]}"


def test_disabled_service_batch_reward_bit_identical(patient_sympy):
    """reward_service disabled (the default): batch_reward over the whole
    corpus is bit-identical to direct local grading — the acceptance
    contract for the off-by-default switch."""
    from areal_tpu.rewards import client as rc

    rc.configure_service(None)  # explicit: no service mode
    entries = _corpus()
    tasks = [{"task": "math", "generated": e["generated"],
              "solutions": e["solutions"]} for e in entries]
    got = batch_reward(tasks)
    direct = [verify_math(e["generated"], e["solutions"]) for e in entries]
    assert got == direct
    assert got == [e["expected"] for e in entries]
