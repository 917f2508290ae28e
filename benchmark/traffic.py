"""The one general traffic generator: a traffic file's parameters plus
``--seed`` give the run's inputs. Pure numpy, no jax, no program code.

Shapes and contents are drawn apart on purpose. The trainer compiles one
program per packed ``[R, L]`` grid and the generation server one per
distinct (rows, capacity): lengths drawn from ``--seed`` would make every
run compile new programs, so no run after the first would find its
programs in the cache. The LENGTHS therefore come from the traffic file's
own ``shape_seed`` (they are part of the mix, like its medians), and
``--seed`` draws everything else: token ids, rewards and (in the drivers)
the weights.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np


@dataclasses.dataclass
class Group:
    """One prompt sampled ``group_size`` times with a shared budget."""

    prompt_ids: np.ndarray  # [prompt_len] int32
    new_tokens: int


def _lognormal_int(rng, p: Dict, n: int) -> np.ndarray:
    """``n`` draws of a lognormal clipped to [min, max]; with
    ``multiple_of`` rounded to the nearest multiple first (a budget that
    is a whole number of chunks: the server compiles a decode program for
    every distinct tail-chunk length)."""
    x = np.exp(rng.normal(np.log(p["median"]), p["sigma"], n))
    m = int(p.get("multiple_of", 1))
    return np.clip(np.rint(x / m) * m, p["min"], p["max"]).astype(np.int64)


def draw_lengths(shape: Dict, n_groups: int) -> Dict[str, np.ndarray]:
    """Prompt and new-token lengths of ``n_groups`` groups — a function of
    the traffic file alone (``shape_seed``)."""
    rng = np.random.default_rng(int(shape["shape_seed"]))
    p, g = shape["prompt_len"], shape["new_tokens"]
    return {
        "prompt_len": _lognormal_int(rng, p, n_groups),
        "new_tokens": _lognormal_int(rng, g, n_groups),
    }


def draw_token_ids(rng, n: int, vocab_size: int, reserved: int) -> np.ndarray:
    """Uniform over the vocabulary without the first ``reserved`` ids
    (pad 0, eos 1)."""
    return rng.integers(reserved, vocab_size, n, dtype=np.int64).astype(
        np.int32)


def make_groups(shape: Dict, n_groups: int, seed: int, vocab_size: int,
                ) -> List[Group]:
    lens = draw_lengths(shape, n_groups)
    rng = np.random.default_rng([int(seed), 1])
    return [
        Group(draw_token_ids(rng, int(pl), vocab_size,
                             int(shape.get("reserved_ids", 2))), int(nt))
        for pl, nt in zip(lens["prompt_len"], lens["new_tokens"])
    ]


def make_train_batches(shape: Dict, n_batches: int, prompts_per_batch: int,
                       group_size: int, seed: int, vocab_size: int,
                       ) -> List[Dict[str, np.ndarray]]:
    """``n_batches`` packed trajectory batches of ``prompts_per_batch`` x
    ``group_size`` sequences: each group shares its prompt and its
    new-token count. Returns flat arrays in the key layout the rollout
    worker pushes (``partial_rollout.trajectory_from_gen``), minus the
    behaviour logprobs, which the driver computes."""
    groups = make_groups(shape, n_batches * prompts_per_batch, seed,
                         vocab_size)
    rng = np.random.default_rng([int(seed), 2])
    reserved = int(shape.get("reserved_ids", 2))
    out = []
    for b in range(n_batches):
        toks, pmask, seqlens, gids = [], [], [], []
        for gi in range(prompts_per_batch):
            g = groups[b * prompts_per_batch + gi]
            for _ in range(group_size):
                gen = draw_token_ids(rng, g.new_tokens, vocab_size, reserved)
                toks += [g.prompt_ids, gen]
                pmask += [np.ones(len(g.prompt_ids), np.int32),
                          np.zeros(g.new_tokens, np.int32)]
                seqlens.append(len(g.prompt_ids) + g.new_tokens)
                gids.append(f"b{b}g{gi}")
        n_seq = len(seqlens)
        out.append({
            "packed_input_ids": np.concatenate(toks).astype(np.int32),
            "prompt_mask": np.concatenate(pmask),
            "seqlens": np.asarray(seqlens, np.int64),
            "group": gids,
            "rewards": rng.integers(0, 2, n_seq).astype(np.float32) * 2 - 1,
        })
    return out
