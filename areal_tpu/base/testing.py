"""Test fixtures: mock tokenizer + fabricated datasets.

Parity target: ``realhf/base/testing.py`` (tiny fabricated models + random
WordPiece tokenizer) and ``tests/fixtures.py`` (random jsonl datasets).
The tiny model configs live in models/config.py (tiny_config).
"""

from __future__ import annotations

import json
import random
from typing import List, Optional

PAD_TOKEN = 0
EOS_TOKEN = 1


class MockTokenizer:
    """Deterministic char-level tokenizer: byte + 2 (0 = pad, 1 = eos)."""

    def __init__(self, vocab_size: int = 258):
        self.vocab_size = vocab_size
        self.pad_token_id = PAD_TOKEN
        self.eos_token_id = EOS_TOKEN

    def encode(self, text: str) -> List[int]:
        return [(b % (self.vocab_size - 2)) + 2 for b in text.encode()]

    def decode(self, ids) -> str:
        # A model's vocabulary may be far wider than this tokenizer's 256
        # bytes (a real checkpoint under mock_tokenizer): wrap, don't raise.
        return bytes(
            (int(i) - 2) % 256 for i in ids
            if int(i) not in (PAD_TOKEN, EOS_TOKEN)
        ).decode(errors="replace")

    def __call__(self, texts, **kw):
        if isinstance(texts, str):
            texts = [texts]
        return {"input_ids": [self.encode(t) for t in texts]}


def make_math_jsonl(path: str, n: int = 32, seed: int = 0) -> List[dict]:
    """Solvable arithmetic prompts with boxed ground truths."""
    rng = random.Random(seed)
    records = []
    for i in range(n):
        a, b = rng.randint(0, 50), rng.randint(0, 50)
        records.append(
            {
                "query_id": f"q{i}",
                "prompt": f"What is {a}+{b}? ",
                "task": "math",
                "solutions": [f"\\boxed{{{a + b}}}"],
            }
        )
    with open(path, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")
    return records


def make_sft_jsonl(path: str, n: int = 32, seed: int = 0) -> List[dict]:
    rng = random.Random(seed)
    records = []
    for i in range(n):
        a, b = rng.randint(0, 50), rng.randint(0, 50)
        records.append(
            {
                "query_id": f"s{i}",
                "prompt": f"What is {a}+{b}? ",
                "answer": f"The answer is {a + b}.",
            }
        )
    with open(path, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")
    return records


def make_code_jsonl(path: str, n: int = 4, seed: int = 0) -> List[dict]:
    rng = random.Random(seed)
    records = []
    for i in range(n):
        k = rng.randint(1, 5)
        io = {
            "inputs": [f"{x}\n" for x in range(3)],
            "outputs": [f"{x + k}\n" for x in range(3)],
        }
        records.append(
            {
                "query_id": f"c{i}",
                "prompt": f"Write a program that reads x and prints x+{k}.",
                "task": "code",
                "solutions": [],
                "input_output": json.dumps(io),
            }
        )
    with open(path, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")
    return records


def make_mixed_jsonl(path: str, n_math: int = 6, n_code: int = 2,
                     seed: int = 0) -> List[dict]:
    """Mixed math+code RL fixture: the code-RL e2e / pass@k eval dataset
    shape (docs/rewards.md). Math records carry boxed solutions; code
    records carry stdin/stdout ``input_output`` cases a one-liner can
    pass — graded by the sandbox, fully solvable in principle."""
    rng = random.Random(seed)
    records = []
    for i in range(n_math):
        a, b = rng.randint(0, 50), rng.randint(0, 50)
        records.append({
            "query_id": f"m{i}",
            "prompt": f"What is {a}+{b}? ",
            "task": "math",
            "solutions": [f"\\boxed{{{a + b}}}"],
        })
    for i in range(n_code):
        k = rng.randint(1, 5)
        io = {
            "inputs": [f"{x}\n" for x in range(2)],
            "outputs": [f"{x + k}\n" for x in range(2)],
        }
        records.append({
            "query_id": f"c{i}",
            "prompt": f"Write a program that reads x and prints x+{k}. ",
            "task": "code",
            "solutions": [],
            "input_output": json.dumps(io),
        })
    with open(path, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")
    return records


def bench_trajectory_dist(seed: int = 0, n_seq: int = 32):
    """A PPO trajectory length distribution — ~250-token prompts +
    ~640-token generations — as ``(rng, plens, glens)``:
    tests/test_packing_fill.py builds packing-only samples from it.
    Change it here and the fill numbers and the ≥0.92 gate move
    together."""
    import numpy as np

    rng = np.random.RandomState(seed)
    plens = rng.randint(200, 257, n_seq)
    glens = rng.randint(512, 769, n_seq)
    return rng, plens, glens


def bench_trajectory_sample(seed: int = 0, n_seq: int = 32,
                            vocab: int = 1000):
    """``(SequenceSample, seqlens)`` carrying only packed_input_ids — what
    packing-fill consumers of :func:`bench_trajectory_dist` need."""
    import numpy as np

    from areal_tpu.api.data import SequenceSample

    rng, plens, glens = bench_trajectory_dist(seed, n_seq)
    seqlens = (plens + glens).astype(int)
    toks = rng.randint(2, vocab, int(seqlens.sum())).astype(np.int32)
    return SequenceSample.from_default(
        ids=[f"b{i}" for i in range(n_seq)],
        data={"packed_input_ids": toks},
        seqlens=seqlens.tolist(),
    ), seqlens
