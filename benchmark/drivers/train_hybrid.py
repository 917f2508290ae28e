"""Driver ``train_hybrid``: ``train_share``'s step — ``PPOActorInterface``
``inference`` then ``train_step`` on packed trajectory batches, on ONE
chip's share of a model whose layers are shared by a group of chips —
for a hybrid model (``model_type`` nemotron_h): Mamba-2 layers, latent
expert layers beside a shared expert, one attention layer; the
configuration holds a share of every mixer's heads, ``n_routed_experts``
of the ``num_routed_experts`` the router scores and a slice of the
vocabulary, and the program runs them with no other chip and nothing
standing in for one.

It is ``drivers/train_share.py`` where it can be (the unit-scale
embedding, the inference pass's grid counter and the reference call are
imported from there; the experiment from
``drivers/train_ep.py``, the sample layout and the packer's counter from
``drivers/train.py``) and differs in its limits and checks, which are
constants of this file:

 - ``correct`` wants: platform, finite loss and grad-norm every step, the
   first importance weight within 0.05 of 1, 0 compiles in the window, no
   (token, expert) pair dropped in any step (as ``train_share``); the
   train step's attention traced to the flash kernel — on the one ``*``
   layer — and to nothing else; the pairs that landed on this chip
   within ``LOCAL_SHARE`` of those routed (8 of 512 experts held); no
   bounded expert pass on the whole buffer (``moe_full_passes`` 0 in
   every step: a pass that fell back is slower, not wrong, and would
   else show as throughput noise); the engine's logprobs of a trajectory
   THE PACKER PLACED SECOND OR LATER IN ITS ROW (so every Mamba layer
   reset its scan and its convolution in front of it), its first
   ``REFERENCE_TOKENS`` tokens, against the configuration's reference
   run on that trajectory alone, within the tolerances below — over all
   of them, and over the ``HEAD_TOKENS`` just behind the boundary, where
   a missing reset shows; and, on the same tokens, the routed part of
   the first expert layer alone against the reference's
   (``held_experts_error``: the held experts' precision, which the
   logprobs cannot see);
 - ``n_params`` is the share's (``ssm_cost.share_params``);
 - the scans the traced steps ran (``ssm_calls_traced``), the program's
   trace-time count of them and the share's routing counters go into the
   records for the per-layer metrics ``ssm_*`` and ``latent_*``.
"""

from __future__ import annotations

import functools
import importlib
import math
import os
import statistics
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import driverlib as dl  # noqa: E402
from benchmark import harness, readers, ssm_cost, traffic  # noqa: E402
from benchmark.drivers.train import PackCounter, to_sample  # noqa: E402
from benchmark.drivers.train_ep import build_experiment  # noqa: E402
from benchmark.drivers.train_share import (  # noqa: E402
    EMBED_SCALE, InferGrids, reference_logprobs)

REFERENCE_TOKENS = 2048  # of a trajectory placed second or later in a row

# Engine logprobs (bf16 compute; the chunked scan with float32 decays and
# states; sorted grouped GEMMs over the 8 held experts; the flash kernel)
# against reference_nemotron_h (float32 at "highest", the recurrence a
# token at a time, every held expert on every token), over the first 2048
# tokens of a trajectory that sits SECOND in its packed row (a row of this
# cell holds one or two), behind a document of 1028 tokens. SET FROM the
# chip (my chip runs, PR 34; PERF.md section 2 has every seed's reading),
# 29 seeds: 0.0114-0.0124 nat on average; 0.076-0.268 at the worst token —
# a heavy tail (median 0.119): where two experts' scores tie, bfloat16 and
# float32 choose differently and one token's output jumps. The mean limit
# is 1.21 x the largest measured; the max limit 1.68 x (a first limit of
# 0.25, set from four seeds that read 0.088-0.112, refused the eighth seed
# of the next eight, 0.268). What fails them, the same engine against a
# WRONG reference (benchmark/check_limits_nemotron_h.py; mean / max over
# 7 seeds): the norm before the gate 0.097-0.102 / 0.44-0.76; the gates
# not scaled by 5 0.038-0.041 / 0.24-0.30; the choice without its bias
# 0.023-0.027 / 0.19-0.27 (by the mean limit alone); silu for relu²
# 0.82-0.85 / 3.5-4.4; every matrix product in float8_e4m3, the nearest
# precision below the configuration's bfloat16, 0.145-0.151 / 0.62-0.80.
# THE RESET LEFT OFF (the Mamba layers run over the row's two documents as
# one; 5 seeds) moves the mean by a tenth (0.0128-0.0149) and the max to
# 0.36-0.98 — two seeds of five UNDER the max limit — because all of it
# sits in the ten tokens behind the boundary (0.98, 0.41, 0.16, 0.17,
# 0.19, 0.21 ... against 0.002-0.03 in a sound run): a head's state forgets
# within tens of tokens at these decays. So the reset has a limit of its
# own, the mean error of the first HEAD_TOKENS logprobs: a sound run reads
# 0.0100-0.0135 (the five seeds it was set from; the final tree's six then
# read 0.0065-0.0196; a token as far off as any ever seen, 0.268, among
# the 16 would make 0.027), the reset left off 0.081-0.175; the limit is
# 1.8 x the largest of the one and 2.3 x under the smallest of the other.
LOGPROB_MAX_ERR = 0.45
LOGPROB_MEAN_ERR = 0.015
HEAD_TOKENS = 16  # the logprobs just behind the row's boundary
LOGPROB_HEAD_ERR = 0.035
# held_experts_error, the routed part of the first expert layer alone:
# the logprobs do not move when the 8 held experts' inputs and weights are
# rounded to float8_e4m3 (+0.0004 on the mean: 1.6 % of the pairs land
# here), this does. Two seeds (my chip runs, PR 34; 514 and 573 of 2048
# tokens chose a held expert): median 0.00637 and 0.00640 as published
# (0.00635-0.00640 in the final tree's six runs), 0.0597 and 0.0598 with
# the experts in float8, 0.0925 and 0.0930 with every product in float8.
# The limit is 3.1 x the one and 3.0 x under the other. (The mean, 0.015-0.016, carries the few tokens whose 22nd choice
# differs.) What still fails NO limit and is said so in PERF.md section 7:
# the scan's state rounded to bfloat16 after every token (0.0116 / 0.111,
# the sound reading to the digit).
EXPERTS_MEDIAN_REL_ERR = 0.02
# (token, expert) pairs on this chip over pairs routed: 8 / 512 = 0.0156
# under an even router. Measured 0.0146-0.0176 by seed (busiest expert
# 2.4-2.6 x the mean); the band is 0.7 x to 1.35 x the even share.
LOCAL_SHARE = (0.011, 0.021)


def compare_logprobs(got: np.ndarray, ref: np.ndarray) -> Dict[str, Any]:
    cmp = dl.compare_logprobs(got, ref)
    err = np.abs(np.asarray(got, np.float64) - np.asarray(ref, np.float64))
    cmp["head_mean_err"] = float(err[:HEAD_TOKENS].mean())
    cmp["ok"] = bool(np.isfinite(np.asarray(got, np.float64)).all()
                     and cmp["max_err"] <= LOGPROB_MAX_ERR
                     and cmp["mean_err"] <= LOGPROB_MEAN_ERR
                     and cmp["head_mean_err"] <= LOGPROB_HEAD_ERR)
    return cmp


def held_experts_error(engine, cfg_file: Dict[str, Any], toks,
                        ) -> Dict[str, Any]:
    """The HELD EXPERTS' part alone, which the logprobs cannot see (1.6 %
    of the pairs land here): the routed part of the FIRST expert layer on
    ``toks`` — the program's ``moe.moe_mlp`` on that layer's weights as
    the engine computes with them (its compute-dtype copy), the shared
    expert left out of the tree, against the reference's ``routed``
    (float32 masters, "highest"), both on the SAME input (the normed
    embedding, rounded to the compute dtype) — as the MEDIAN, over the
    tokens that chose a held expert, of |difference| / |reference|. The
    median: a token whose 22nd choice differs between bfloat16 and
    float32 scores is off by a whole expert, and a few are."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.models import moe

    reference = importlib.import_module("benchmark." + cfg_file["reference"])
    layer = {k: w[0] for k, w in engine.params["layers"]["moe_only"].items()}
    copy = {k: w[0] for k, w
            in engine.compute_params()["layers"]["moe_only"].items()
            if k not in ("ln", "s_up", "s_down")}
    toks = jnp.asarray(toks, jnp.int32)
    with jax.default_matmul_precision("highest"):
        u = reference._rms(reference.f32(engine.params["embedding"][toks]),
                           reference.f32(layer["ln"]),
                           reference.eps_of(cfg_file))
        u = u.astype(copy["e_up"].dtype)
        want = np.asarray(reference.routed(reference.f32(u), cfg_file, layer),
                          np.float64)
    got, _ = jax.jit(functools.partial(moe.moe_mlp, moe=engine.cfg.moe))(
        u[None], copy)
    got = np.asarray(got[0], np.float64)
    size = np.linalg.norm(want, axis=-1)
    rel = np.linalg.norm(got - want, axis=-1)[size > 0] / size[size > 0]
    return {"tokens": int(rel.size), "median_rel_err": float(np.median(rel)),
            "mean_rel_err": float(rel.mean()),
            "ok": bool(rel.size > 0
                       and np.median(rel) <= EXPERTS_MEDIAN_REL_ERR)}


def build_model(spec: Dict[str, Any], exp):
    """``drivers/train_share.build_model`` — the program's own init from
    ``--seed``, the embedding at unit scale — with the shared expert's
    down-projection CENTRED over its inputs. ``relu²`` is never negative:
    its mean (0.82 at the program's scales) goes through a random
    ``s_down`` to a vector that is the SAME for every token, 1.2 a
    dimension against the unit-scale embedding's 1.0, a layer; behind
    five such layers the router mostly sees that shared direction, the
    busiest expert gets 6-8 x the mean load, the 8 held experts' share of
    the pairs moves between 0.012 and 0.021 with the seed, one seed in
    ten sends some passes over the bounded pass's rows (whole-buffer
    passes, -2.4 % throughput) and the runs spread by 0.9 % (my chip
    runs, PR 34; PERF.md section 2). A trained model's shared expert has
    learnt not to shout one direction; centring takes the constant's
    image out and leaves every scale as drawn."""
    import areal_tpu.algorithms  # noqa: F401 — registers the interfaces
    import areal_tpu.backend.jax_train  # noqa: F401 — registers the backend
    from areal_tpu.api.model import Model, make_backend, make_interface
    from benchmark import weights

    tcfg = exp.build_trainer_config(async_mode=True)
    rc = tcfg.models["actor"]
    model_cfg = weights.model_config(spec["config"])
    params = weights.make_params(model_cfg, spec["seed"])
    moe = dict(params["layers"]["moe_only"])
    moe["s_down"] = moe["s_down"] - moe["s_down"].mean(axis=-2, keepdims=True)
    params = {**params, "embedding": params["embedding"] * EMBED_SCALE,
              "layers": {**params["layers"], "moe_only": moe}}
    backend = make_backend(rc.backend, **{"train": rc.train,
                                          **rc.backend_args})
    model = backend.initialize(Model("actor", (model_cfg, params)),
                               tcfg.ft_spec)
    ifaces = {
        name: make_interface(tcfg.mfcs[name].interface,
                             **tcfg.mfcs[name].interface_args)
        for name in ("actor_inf", "actor_train")
    }
    return model, ifaces, tcfg


class Placements:
    """Where the packer put each trajectory of the LAST batch the engine's
    ``forward`` (the inference pass) split: ``{index in the sample:
    (micro-batch, row, first column)}``, read around the packer while
    ``forward`` runs. Each micro-batch is a grid of its own: a row is one
    (micro-batch, row) pair."""

    def __init__(self, engine):
        from areal_tpu.backend import microbatch as mbu

        self.at: Dict[int, Tuple[int, int, int]] = {}
        self._mbs = 0  # micro-batches of this forward so far
        self._inside = False
        inner_forward, inner_split = engine.forward, mbu.split_into_microbatches

        def forward(*a, **kw):
            self._inside, self.at, self._mbs = True, {}, 0
            try:
                return inner_forward(*a, **kw)
            finally:
                self._inside = False

        def split(*a, **kw):
            mbs = inner_split(*a, **kw)
            if self._inside:
                for mb in mbs:
                    for i, (row, col) in zip(mb.sample_indices,
                                             mb.layout.placements):
                        self.at[int(i)] = (self._mbs, int(row), int(col))
                    self._mbs += 1
            return mbs

        engine.forward = forward
        mbu.split_into_microbatches = split


def placed_later(ifaces, model, inf_spec, sample, placements: Placements,
                 ) -> Optional[Tuple[np.ndarray, np.ndarray, Dict[str, int]]]:
    """(engine logprobs, tokens, where) of the first ``REFERENCE_TOKENS``
    tokens of the longest trajectory of ``sample`` that the packer placed
    behind another in its row, out of ONE inference pass over the whole
    batch — a causal prefix of a document stands alone. None where every
    trajectory starts its row."""
    prox = ifaces["actor_inf"].inference(
        model, sample, inf_spec).data["prox_logprobs"]
    lens = [int(n) for n in sample.total_lens("packed_input_ids")]
    later = [i for i, (_, _, col) in placements.at.items() if col > 0]
    if not later:
        return None
    i = max(later, key=lambda j: lens[j])
    start = sum(lens[:i])
    n_ref = min(lens[i], REFERENCE_TOKENS)
    toks = np.asarray(
        sample.data["packed_input_ids"][start:start + n_ref])
    mb, row, col = placements.at[i]
    ahead = sorted((c, j) for j, (m, r, c) in placements.at.items()
                   if (m, r) == (mb, row) and c < col)
    where = {"trajectory": i, "micro_batch": mb, "row": row, "column": col,
             "tokens": n_ref, "ahead_in_row": [j for _, j in ahead]}
    return np.asarray(prox[start + 1:start + n_ref]), toks, where


def ssm_calls(cfg: Dict[str, Any], infer_grids: Dict[str, int],
              train_grids: Dict[str, int], remat: bool,
              ) -> List[Dict[str, Any]]:
    """The scans some steps ran, for the roofline: each micro-batch of a
    grid ``RxL`` runs one scan a Mamba layer a pass — forward in the
    inference pass; in the train pass forward, the forward its backward
    re-runs (no checkpoint policy keeps a scan's products: they carry
    batch dimensions) and backward."""
    layers = ssm_cost.layer_counts(cfg)["M"]
    calls = []
    for grids, train in ((infer_grids, False), (train_grids, True)):
        for key, n_mbs in grids.items():
            R, L = (int(x) for x in key.split("x"))
            n = n_mbs * layers
            calls.append({
                "rows": R, "length": L, "chunk": cfg["chunk_size"],
                "heads": cfg["mamba_num_heads"], "groups": cfg["n_groups"],
                "fwd": n * (2 if train and remat else 1),
                "bwd": n if train else 0})
    return calls


def main() -> int:
    spec = dl.load_spec()
    t, out = spec["traffic"], spec["out"]
    split: Dict[str, float] = {"imports_s": time.time() - spec["t0"]}
    t_mark = time.time()
    import jax

    from areal_tpu.base.compile_watch import enable_compilation_cache
    from areal_tpu.ops import attention

    enable_compilation_cache()
    device = dl.require_device(spec)
    exp = build_experiment(spec)
    model, ifaces, tcfg = build_model(spec, exp)
    engine = model.module
    split["weights_backend_s"] = time.time() - t_mark
    state_bytes = (jax.local_devices()[0].memory_stats() or {}).get(
        "bytes_in_use")
    inf_spec, train_spec = exp.actor_inf.mb_spec, exp.actor_train.mb_spec
    packs = PackCounter(engine)
    infer = InferGrids(engine)
    placements = Placements(engine)
    dl.wrap_span(engine, "train_uniform", "train/dispatch_minibatch")
    dl.wrap_span(engine, "run_prep", "train/advantage_prep")
    dl.wrap_span(engine, "forward", "train/inference_forward")

    n_prompts = exp.dataset.train_bs_n_seqs
    raw = traffic.make_train_batches(
        t["shape"], t["n_batches"], n_prompts, exp.group_size, spec["seed"],
        spec["config"]["vocab_size"])

    def step(sample) -> Dict[str, float]:
        """One trainer step of the async recipe; ends on the host with the
        step's statistics, so the device has finished."""
        with dl.span("train/actor_inf"):
            sample.update_(ifaces["actor_inf"].inference(
                model, sample, inf_spec))
        with dl.span("train/actor_train"):
            return ifaces["actor_train"].train_step(model, sample, train_spec)

    # Set-up, as in ``train_share``: behaviour logprobs by the same engine,
    # then every batch warmed once, then one more forward of each.
    t_mark = time.time()
    samples, warm_stats = [], []
    for i, b in enumerate(raw):
        b["packed_logprobs"] = np.zeros(len(b["packed_input_ids"]), np.float32)
        s = to_sample(b, f"b{i}")
        prox = ifaces["actor_inf"].inference(model, s, inf_spec)
        s.data["packed_logprobs"] = (
            prox.data["prox_logprobs"] * (1 - b["prompt_mask"])
        ).astype(np.float32)
        samples.append(s)
    for s in samples:
        warm_stats.append(step(s))
    for s in samples:
        ifaces["actor_inf"].inference(model, s, inf_spec)
    split["warmup_s"] = time.time() - t_mark
    split["compile_cache_after_warmup"] = dl.cache_counts()
    grids = dict(packs.shapes)  # every train grid of the mix: n_mbs x R x L
    packs.reset()
    infer.grids = {}

    n = len(samples)
    batch_tokens = [int(sum(s.total_lens("packed_input_ids")))
                    for s in samples]
    trace = dl.TraceWindow(out) if spec["trace"] else None
    stats: List[Dict[str, float]] = []
    steps: List[Dict[str, Any]] = []
    window_start = time.time()
    t0 = time.monotonic()
    elapsed = 0.0
    while elapsed < spec["seconds"]:
        i = len(steps)
        if trace and i == n:
            trace.start()
        traced = bool(trace and trace.on)
        train_before, infer_before = dict(packs.shapes), dict(infer.grids)
        stats.append(step(samples[i % n]))
        if traced and i + 1 == 2 * n:
            trace.stop()
        now = time.monotonic() - t0
        # micro-batches of this step, by packed grid "RxL", of each pass
        train_mbs: Dict[str, int] = {}
        for k, c in packs.shapes.items():
            n_mbs, R, L = k.split("x")
            d = int(n_mbs) * (c - train_before.get(k, 0))
            if d:
                train_mbs[f"{R}x{L}"] = train_mbs.get(f"{R}x{L}", 0) + d
        infer_mbs = {k: c - infer_before.get(k, 0)
                     for k, c in infer.grids.items()
                     if c - infer_before.get(k, 0)}
        steps.append({"batch": i % n, "secs": now - elapsed, "traced": traced,
                      "train_mbs": train_mbs, "infer_mbs": infer_mbs})
        elapsed = now
    if trace:
        trace.stop()
    memory_peak = dl.memory_peak_bytes()  # before the reference's forward
    cache_end = dl.cache_counts()
    warm = split["compile_cache_after_warmup"]
    window_compiles = cache_end.get("misses", 0) - warm.get("misses", 0)
    window_cache_hits = cache_end.get("hits", 0) - warm.get("hits", 0)
    thr = readers.window_throughput(steps, batch_tokens)

    cfg_file = spec["config"]
    notes: List[str] = []
    bad_steps = sum(
        not (math.isfinite(st["actor_loss"]) and math.isfinite(st["grad_norm"])
             and st["grad_norm"] > 0) for st in stats)
    first_imp = warm_stats[0]["importance_weight"]
    attn = attention.dispatch_counts()
    has_attention = ssm_cost.layer_counts(cfg_file)["*"] > 0
    want = (set() if not has_attention else
            {"pallas"} if spec["platform"] == "tpu" else {"reference"})
    kernel_ok = set(attn.get("train", {})) == want
    every = warm_stats + stats
    dropped = [st.get("moe_dropped_frac") for st in every]
    dropless = all(d == 0.0 for d in dropped)
    local = [st.get("moe_local_rows", float("nan")) / st["moe_routed_rows"]
             for st in every]
    share_ok = all(LOCAL_SHARE[0] <= x <= LOCAL_SHARE[1] for x in local)
    # every bounded expert pass ran on its bounded rows (nan: no such count)
    full_passes = sum(st.get("moe_full_passes", float("nan")) for st in every)
    # a trajectory behind another in its row, against the reference alone
    found = next((r for r in (placed_later(ifaces, model, inf_spec, s,
                                           placements) for s in samples)
                  if r is not None), None)
    if found is None:
        cmp, where = {"ok": False, "why": "no trajectory placed later"}, None
    else:
        got, toks, where = found
        cmp = compare_logprobs(
            got, reference_logprobs(engine.params, cfg_file, toks))
        cmp["held_experts"] = held_experts_error(engine, cfg_file, toks)
        cmp["ok"] = cmp["ok"] and cmp["held_experts"]["ok"]
    correct = (bad_steps == 0 and abs(first_imp - 1.0) < 0.05 and kernel_ok
               and dropless and share_ok and full_passes == 0 and cmp["ok"]
               and window_compiles == 0 and thr["tok_s"] is not None)

    geometry: Dict[str, int] = {}
    try:
        from areal_tpu.models import ssm

        geometry = {"%dx%d/%d/h%dg%d" % g: c
                    for g, c in ssm.geometry_counts().items()}
    except ImportError:  # a program without the mixer
        pass
    remat_plan = engine.remat_plan()

    def summed(key: str, only_traced: bool) -> Dict[str, int]:
        tot: Dict[str, int] = {}
        for x in steps:
            if x["traced"] or not only_traced:
                for g, c in x[key].items():
                    tot[g] = tot.get(g, 0) + c
        return tot

    calls_traced = ssm_calls(cfg_file, summed("infer_mbs", True),
                             summed("train_mbs", True), bool(remat_plan))
    traced_steps = [(st, x) for st, x in zip(stats, steps) if x["traced"]]
    load_ratio = [st["moe_expert_load_ratio"] for st in stats]
    notes.append(f"steps={len(steps)} window={elapsed:.3f}s "
                 f"tok_s={thr['tok_s']} mean_tok_s={thr['mean_tok_s']} "
                 f"slow_step_s={thr['slow_step_s']} "
                 f"step_secs={[round(x['secs'], 3) for x in steps]} "
                 f"batch_tokens={batch_tokens} "
                 f"first_importance_weight={first_imp:.4f} attention={attn} "
                 f"moe_dropped_frac_max={max(dropped)} "
                 f"moe_local_share={min(local):.5f}..{max(local):.5f} "
                 f"moe_expert_load_ratio={statistics.fmean(load_ratio):.4f} "
                 f"moe_full_passes={full_passes} "
                 f"reference={cmp} reference_of={where} "
                 f"window_compiles={window_compiles} "
                 f"window_cache_hits={window_cache_hits} "
                 f"grids={grids} infer_grids={summed('infer_mbs', False)} "
                 f"remat_plan={remat_plan} ssm_geometry={geometry} "
                 f"state_bytes={state_bytes} hbm_peak={memory_peak} "
                 f"setup_split={split}")

    red = trace.reduce() if trace else {}
    records = {
        "device": device, "chips": int(spec["cell"]["chips"]),
        "window_s": elapsed, "config": cfg_file,
        "counters": {
            "steps": len(steps), "batch_tokens": batch_tokens, **thr,
            "pack_real_tokens": packs.real,
            "pack_padded_tokens": packs.padded,
            "pack_shapes": packs.shapes,
            "window_compiles": window_compiles,
            "window_cache_hits": window_cache_hits,
            "n_params": ssm_cost.share_params(cfg_file),
            "state_bytes": state_bytes,
            "moe_expert_load_ratio": statistics.fmean(load_ratio),
            "moe_dropped_frac_max": max(dropped),
            # (token, expert) pairs per layer over the window's steps:
            # routed over all experts, and landed on the held ones
            "moe_routed_rows": sum(st["moe_routed_rows"] for st in stats),
            "moe_local_rows": sum(st.get("moe_local_rows", 0.0)
                                  for st in stats) if all(
                "moe_local_rows" in st for st in stats) else None,
            # of the traced steps, and their micro-batches (each one
            # grouped-GEMM call a layer a pass)
            "moe_local_rows_traced": sum(
                st.get("moe_local_rows", 0.0) for st, _ in traced_steps),
            "moe_mbs_traced": sum(sum(x["train_mbs"].values())
                                  for _, x in traced_steps),
            # the scans as the program traced them, and those the traced
            # steps ran
            "ssm_geometry": geometry,
            "ssm_calls_traced": calls_traced,
        },
        "memory_peak_bytes": memory_peak,
        "trace": red, "setup_split": split,
    }
    result = {
        "correct": bool(correct), "attempted": len(stats),
        "failed": int(bad_steps),
        "end_to_end": {
            "train_tok_s_chip": ((thr["tok_s"] or 0.0)
                                 / int(spec["cell"]["chips"])),
            "setup_s": window_start - spec["t0"],
        },
        "device": {**device, "memory_peak_bytes": records["memory_peak_bytes"],
                   **({"busy_s": red["busy_s"], "window_s": red["window_s"]}
                      if red else {})},
        "breakdown": dl.breakdown(red),
        "records": records, "notes": notes,
    }
    harness.write_json(os.path.join(out, "result.json"), result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
