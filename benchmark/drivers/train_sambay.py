"""Driver ``train_sambay``: ``train_hybrid``'s step — ``PPOActorInterface``
``inference`` then ``train_step`` on packed trajectory batches, on ONE
chip — for a decoder-hybrid-decoder model (``model_type`` phi4flash;
SambaY): Mamba-1 blocks (a selective scan), window / full differential
attention, and behind them gated memory units and cross-attention layers
that read the memory and the K/V ONE earlier layer made; the configuration
is a contiguous cut of the published layers with every width whole and a
slice of the vocabulary, and the program runs it with no other chip and
nothing standing in for one.

It is ``drivers/train_hybrid.py`` where it can be (the packer's
placements, the inference pass's grid counter, the reference call and the
windowed kernel's calls are imported from there and from
``drivers/train_share.py``; the experiment from ``drivers/train_ep.py``,
the sample layout and the packer's counter from ``drivers/train.py``) and
differs in its limits and checks, which are constants of this file:

 - ``correct`` wants: platform, finite loss and grad-norm every step, the
   first importance weight within 0.05 of 1, 0 compiles in the window; the
   train step's attention traced to the windowed kernel (the S layer) and
   the flash kernel (F AND X: a cross layer goes through the same
   dispatch) and to nothing else; every selective scan traced as the
   Pallas kernel; and the engine's logprobs of the first
   ``REFERENCE_TOKENS`` tokens of the batch's longest trajectory THAT THE
   PACKER PLACED BEHIND ANOTHER in its row (so both scans and
   convolutions reset in front of it, and window, full and cross attention
   mask it from the document ahead) against the configuration's reference
   run on that trajectory alone, within the tolerances below — over all
   of them, and over the ``HEAD_TOKENS`` just behind the boundary;
 - ``n_params`` is the cut's (``sambay_cost.share_params``);
 - the scans and windowed calls the traced steps ran, the program's
   trace-time counts of them, ``cross_layer_reads`` and ``blocks`` go
   into the records and notes for the per-layer metrics ``sambay_*``.
"""

from __future__ import annotations

import math
import os
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import driverlib as dl  # noqa: E402
from benchmark import harness, readers, sambay_cost, traffic  # noqa: E402
from benchmark.drivers.train import PackCounter, to_sample  # noqa: E402
from benchmark.drivers.train_ep import build_experiment  # noqa: E402
from benchmark.drivers.train_hybrid import Placements  # noqa: E402
from benchmark.drivers.train_share import (  # noqa: E402
    InferGrids, reference_logprobs, window_calls)

REFERENCE_TOKENS = 4096  # of a trajectory placed second or later: 8 windows

# Engine logprobs (bf16 compute; the selective scan's kernels in float32;
# the windowed and the flash kernel over a value of 128) against
# reference_sambay (float32 at "highest", the recurrence a token at a
# time, a softmax a head), over the first 2569 tokens of the trajectory
# that sits SECOND in its packed row (the longest the packer places
# behind another in this mix: rows hold one or two), behind a document of
# 2569. SET FROM the chip (my chip runs, PR 42; PERF.md section 2 has
# every seed's reading), eight seeds, two of them over 2**31:
# 0.01792-0.01861 nat on average; 0.079-0.103 at the worst token; the 16
# just behind the boundary 0.0163-0.0301 on average. The mean is higher
# than the other cells' 0.006-0.014: every attention layer takes the
# DIFFERENCE of two softmax outputs that a random model makes nearly
# equal (o1 - 0.8 o2 of two running means of v) and RMS-norms it, which
# passes on the bfloat16 rounding of the kernels' outputs five-fold. The
# mean limit is 1.40 x the largest measured, the max limit 2.4 x, the
# head limit 2.5 x. What fails them, the same engine against a WRONG
# reference (benchmark/check_limits_sambay.py, seeds 11 and 2147483659;
# mean / max / head): the scan's and the convolution's reset left off
# 0.0270-0.0286 / 1.11-1.38 / 0.274-0.303 (all of it behind the boundary:
# the max and the head limit refuse it four times over, the mean limit
# barely); the cross layer on the window layer's K/V 0.091-0.092 / 0.43-
# 0.44; the memory taken behind the gate 0.104-0.105 / 0.50-0.52; window
# 1024 0.139-0.141 / 0.77-0.87, no window 0.158-0.168 / 0.95-1.03; every
# matrix product in float8_e4m3, the nearest precision below the
# configuration's bfloat16, 0.236-0.248 / 1.10-1.27 / 0.23-0.28; the
# lambda term dropped 0.242 / 1.04-1.07; the sub-norm dropped 0.249-0.253
# / 1.28-1.35; Delta without its bias 0.411-0.414 / 3.4-5.0; lambda_init
# of layers 0-5 for the published 14-19 0.509-0.525 / 2.6 — every one over
# the mean AND the max limit.
LOGPROB_MAX_ERR = 0.25
LOGPROB_MEAN_ERR = 0.026
HEAD_TOKENS = 16  # the logprobs just behind the row's boundary
LOGPROB_HEAD_ERR = 0.075


def compare_logprobs(got: np.ndarray, ref: np.ndarray) -> Dict[str, Any]:
    cmp = dl.compare_logprobs(got, ref)
    err = np.abs(np.asarray(got, np.float64) - np.asarray(ref, np.float64))
    cmp["head_mean_err"] = float(err[:HEAD_TOKENS].mean())
    cmp["ok"] = bool(np.isfinite(np.asarray(got, np.float64)).all()
                     and cmp["max_err"] <= LOGPROB_MAX_ERR
                     and cmp["mean_err"] <= LOGPROB_MEAN_ERR
                     and cmp["head_mean_err"] <= LOGPROB_HEAD_ERR)
    return cmp


def build_model(spec: Dict[str, Any], exp):
    """``drivers/train.build_model``: the program's own init from
    ``--seed``, as drawn. No embedding scale of the driver's own (the
    share cells draw theirs at unit scale for their routers' sake): the
    head is tied, and a unit-scale embedding makes logits of order
    sqrt(hidden) = 50, on which bfloat16's rounding alone reads 0.5 nat."""
    import areal_tpu.algorithms  # noqa: F401 — registers the interfaces
    import areal_tpu.backend.jax_train  # noqa: F401 — registers the backend
    from areal_tpu.api.model import Model, make_backend, make_interface
    from benchmark import weights

    tcfg = exp.build_trainer_config(async_mode=True)
    rc = tcfg.models["actor"]
    model_cfg = weights.model_config(spec["config"])
    params = weights.make_params(model_cfg, spec["seed"])
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


def placed_later(ifaces, model, inf_spec, sample, placements: Placements,
                 ) -> Optional[Tuple[np.ndarray, np.ndarray, Dict[str, Any]]]:
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


def s6_calls(cfg: Dict[str, Any], infer_grids: Dict[str, int],
             train_grids: Dict[str, int], remat: bool,
             ) -> List[Dict[str, Any]]:
    """The selective scans some steps ran, for the roofline: each
    micro-batch of a grid ``RxL`` runs one scan a Mamba layer a pass —
    forward in the inference pass; in the train pass forward, the forward
    a checkpointed layer re-runs (no entry keeps a kernel's products), the
    forward the backward kernel re-runs inside itself, and backward."""
    layers = sambay_cost.layer_counts(cfg)["M"]
    d_inner, state, _ = sambay_cost.s6_sizes(cfg)
    calls = []
    for grids, train in ((infer_grids, False), (train_grids, True)):
        for key, n_mbs in grids.items():
            R, L = (int(x) for x in key.split("x"))
            n = n_mbs * layers
            calls.append({
                "rows": R, "length": L, "d_inner": d_inner, "state": state,
                "fwd": n * ((3 if remat else 2) if train else 1),
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
    from areal_tpu.models import ssm
    from areal_tpu.ops.pallas import window_attention as wa

    bad_steps = sum(
        not (math.isfinite(st["actor_loss"]) and math.isfinite(st["grad_norm"])
             and st["grad_norm"] > 0) for st in stats)
    first_imp = warm_stats[0]["importance_weight"]
    attn = attention.dispatch_counts()
    layers = sambay_cost.layer_counts(cfg_file)
    on_tpu = spec["platform"] == "tpu"
    # S through the windowed kernel, F and X through the flash kernel (the
    # same dispatch), nothing else; every scan the Pallas kernel
    want = {kernel for kernel, n in (
        ("window", layers["S"]), ("pallas", layers["F"] + layers["X"]))
        if n} if on_tpu else {"reference"}
    s6_geometry = {"%dx%d/d%dn%d/%s" % g: c
                   for g, c in ssm.s6_geometry_counts().items()}
    kernel_ok = set(attn.get("train", {})) == want and all(
        key.endswith("/pallas" if on_tpu else "/xla") for key in s6_geometry)
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
    correct = (bad_steps == 0 and abs(first_imp - 1.0) < 0.05 and kernel_ok
               and cmp["ok"] and window_compiles == 0
               and thr["tok_s"] is not None)

    geometry = wa.geometry_counts()
    geometry_keys = {label: {"%d>%d/%d/w%d" % g: c for g, c in geoms.items()}
                     for label, geoms in geometry.items()}
    remat_plan = engine.remat_plan()

    def summed(key: str, only_traced: bool) -> Dict[str, int]:
        tot: Dict[str, int] = {}
        for x in steps:
            if x["traced"] or not only_traced:
                for g, c in x[key].items():
                    tot[g] = tot.get(g, 0) + c
        return tot

    calls_traced = s6_calls(cfg_file, summed("infer_mbs", True),
                            summed("train_mbs", True), bool(remat_plan))
    window_traced = window_calls(
        geometry, layers["S"], summed("infer_mbs", True),
        summed("train_mbs", True), remat_plan)
    notes.append(f"steps={len(steps)} window={elapsed:.3f}s "
                 f"tok_s={thr['tok_s']} mean_tok_s={thr['mean_tok_s']} "
                 f"slow_step_s={thr['slow_step_s']} "
                 f"step_secs={[round(x['secs'], 3) for x in steps]} "
                 f"batch_tokens={batch_tokens} "
                 f"first_importance_weight={first_imp:.4f} attention={attn} "
                 f"blocks={engine.cfg.block_counts()} "
                 f"cross_layer_reads={engine.cfg.cross_layer_reads} "
                 f"reference={cmp} reference_of={where} "
                 f"window_compiles={window_compiles} "
                 f"window_cache_hits={window_cache_hits} "
                 f"grids={grids} infer_grids={summed('infer_mbs', False)} "
                 f"remat_plan={remat_plan} s6_geometry={s6_geometry} "
                 f"window_geometry={geometry_keys} "
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
            "n_params": sambay_cost.share_params(cfg_file),
            "state_bytes": state_bytes,
            "blocks": engine.cfg.block_counts(),
            "cross_layer_reads": engine.cfg.cross_layer_reads,
            # the scans as the program traced them, and those the traced
            # steps ran
            "s6_geometry": s6_geometry,
            "s6_calls_traced": calls_traced,
            # the train step's windowed calls as the program traced them
            "window_geometry": geometry_keys.get("train", {}),
            "window_calls_traced": window_traced,
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
