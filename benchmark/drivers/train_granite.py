"""Driver ``train_granite``: ``train_sambay``'s step — ``PPOActorInterface``
``inference`` then ``train_step`` on packed trajectory batches, on ONE
chip — for a Granite 4.0-H model (``model_type`` granitemoehybrid,
``num_local_experts`` 0): whole blocks of a Mamba-2 mixer or rope-less GQA
attention, then a dense gated MLP, under the family's four multipliers;
the configuration is one whole period of the published layers with half
of every layer's heads, every width whole and a slice of the vocabulary,
and the program runs it with no other chip and nothing standing in for
one.

It is ``drivers/train_sambay.py`` where it can be (the model from the
program's own init as drawn, the placed-later comparison; the packer's
placements from ``drivers/train_hybrid.py``, the inference pass's grid
counter and the reference call from ``drivers/train_share.py``, the
experiment from ``drivers/train_ep.py``, the sample layout and the
packer's counter from ``drivers/train.py``) and differs in its limits and
checks, which are constants of this file:

 - ``correct`` wants: platform, finite loss and grad-norm every step, the
   first importance weight within 0.05 of 1, 0 compiles in the window; the
   train step's attention traced to the grouped-head causal kernel
   (``{"pallas": n}``) and to nothing else; the scans traced by
   ``ssm.geometry_counts()`` at the configuration's chunk, heads and
   groups, one a run of Mamba blocks a program (the cut ``a . m x9`` is
   one run) on every packed grid; and the engine's logprobs of the first
   4096 tokens (``train_sambay.REFERENCE_TOKENS``:
   the whole trajectory here) of the batch's longest trajectory THAT THE
   PACKER PLACED BEHIND ANOTHER in its row (so every scan and convolution
   reset in front of it, and attention masks it from the documents ahead)
   against the configuration's reference run on that trajectory alone,
   within the tolerances below — over all of them, and over the
   ``HEAD_TOKENS`` just behind the boundary;
 - ``n_params`` is the cut's (``granite_cost.share_params``);
 - the scans the traced steps ran, the program's trace-time counts of
   them, ``blocks`` and the program's gauge ``train/docs_per_row`` go into
   the records and notes for the per-layer metrics ``granite_*``.
"""

from __future__ import annotations

import math
import os
import statistics
import sys
import time
from typing import Any, Dict, List

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import driverlib as dl  # noqa: E402
from benchmark import granite_cost, harness, readers, traffic  # noqa: E402
from benchmark.drivers.train import PackCounter, to_sample  # noqa: E402
from benchmark.drivers.train_ep import build_experiment  # noqa: E402
from benchmark.drivers.train_hybrid import Placements  # noqa: E402
from benchmark.drivers.train_sambay import (  # noqa: E402
    build_model, placed_later)
from benchmark.drivers.train_share import (  # noqa: E402
    InferGrids, reference_logprobs)

# Engine logprobs (bf16 compute; the chunked scan with float32 decays and
# states; the grouped-head causal kernel at scale 1/64) against
# reference_granite_hybrid (float32 at "highest", the recurrence a token
# at a time, a masked softmax), over the whole of the batch's longest
# trajectory that sits BEHIND others in its packed row (2462 tokens behind
# two documents of 4527 in this mix; rows hold 2 to 4). ``logits / 8`` on
# random weights makes logits of order 0.1: every error is an eighth of
# another cell's, so the limits are tight. SET FROM the chip (my chip
# runs, PR 48; PERF.md section 2 has every seed's reading), four seeds,
# one of them over 2**31: 0.00177-0.00179 nat on average; 0.0072-0.0094 at
# the worst token; the 16 just behind the boundary 0.0017-0.0019 on
# average. The mean limit is 1.7 x the largest measured, the max limit
# 2.7 x, the head limit 3.2 x (16 tokens: one as far off as any ever seen
# among them adds 0.0006). What fails them, the same engine against a
# WRONG reference (benchmark/check_limits_granite.py, seeds 11 and
# 2147483659, which themselves read 0.00172-0.00175 / 0.0075-0.0093 /
# 0.0012-0.0019; mean / max / head): the softmax scale 1/8 in place of
# 1/64 0.0065 / 0.066-0.094 / 0.024-0.044 (one attention block of ten,
# behind 0.22: 2.2 x the mean limit, 2.6 x the max limit); THE RESET LEFT
# OFF 0.0021 / 0.108-0.137 / 0.020-0.038 — under the mean limit (a head's
# state forgets within tens of tokens), refused by the max limit 4 x over
# and by the head limit 3 x over; every matrix product in float8_e4m3,
# the nearest precision below the configuration's bfloat16, 0.019-0.020 /
# 0.081-0.087 / 0.018-0.019 — over each limit 3 x and more; the norm
# before the gate 0.062 / 0.27-0.30 / 0.062-0.066; residual_multiplier 1
# 0.107 / 0.43-0.56 / 0.10; embedding_multiplier 1 0.116-0.118 / 0.57-0.60
# / 0.14; logits_scaling 1 2.0 / 6.0-6.4 / 1.7-2.1. No control passes
# every limit.
LOGPROB_MAX_ERR = 0.025
LOGPROB_MEAN_ERR = 0.003
HEAD_TOKENS = 16  # the logprobs just behind the row's boundary
LOGPROB_HEAD_ERR = 0.006
GAUGE = "train/docs_per_row"


def compare_logprobs(got: np.ndarray, ref: np.ndarray) -> Dict[str, Any]:
    cmp = dl.compare_logprobs(got, ref)
    err = np.abs(np.asarray(got, np.float64) - np.asarray(ref, np.float64))
    cmp["head_mean_err"] = float(err[:HEAD_TOKENS].mean())
    cmp["ok"] = bool(np.isfinite(np.asarray(got, np.float64)).all()
                     and cmp["max_err"] <= LOGPROB_MAX_ERR
                     and cmp["mean_err"] <= LOGPROB_MEAN_ERR
                     and cmp["head_mean_err"] <= LOGPROB_HEAD_ERR)
    return cmp


class Gauge:
    """Every value the program publishes under one gauge name, read where
    it calls ``telemetry.set_gauge`` (the benchmark configures no
    registry: the call is a no-op behind this). A program that publishes
    no such gauge leaves ``values`` empty."""

    def __init__(self, name: str):
        from areal_tpu.base import telemetry

        self.values: List[float] = []
        inner = telemetry.set_gauge

        def set_gauge(gauge, v):
            if gauge == name:
                self.values.append(float(v))
            return inner(gauge, v)

        telemetry.set_gauge = set_gauge


def scan_calls(cfg: Dict[str, Any], infer_grids: Dict[str, int],
               train_grids: Dict[str, int], remat: bool,
               ) -> List[Dict[str, Any]]:
    """The chunked scans some steps ran, for the roofline: each
    micro-batch of a grid ``RxL`` runs one scan a Mamba block a pass —
    forward in the inference pass; in the train pass forward, the forward
    its backward re-runs (no checkpoint policy keeps a scan's products:
    they carry batch dimensions) and backward."""
    layers = granite_cost.layer_counts(cfg)["mamba"]
    calls = []
    for grids, train in ((infer_grids, False), (train_grids, True)):
        for key, n_mbs in grids.items():
            R, L = (int(x) for x in key.split("x"))
            n = n_mbs * layers
            calls.append({
                "rows": R, "length": L, "chunk": cfg["mamba_chunk_size"],
                "heads": cfg["mamba_n_heads"],
                "head_dim": cfg["mamba_d_head"],
                "groups": cfg["mamba_n_groups"],
                "state": cfg["mamba_d_state"],
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
    gauge = Gauge(GAUGE)
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
    gauge.values.clear()

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

    bad_steps = sum(
        not (math.isfinite(st["actor_loss"]) and math.isfinite(st["grad_norm"])
             and st["grad_norm"] > 0) for st in stats)
    first_imp = warm_stats[0]["importance_weight"]
    attn = attention.dispatch_counts()
    layers = granite_cost.layer_counts(cfg_file)
    # attention through the grouped-head causal kernel and nothing else
    # (no attention layer in a cut shorter than the period: none traced)
    want = (set() if not layers["attention"] else
            {"pallas"} if spec["platform"] == "tpu" else {"reference"})
    # every scan at the configuration's chunk, heads and groups, one a
    # run of Mamba blocks a program (the cut a . m x9 is one run)
    runs = granite_cost.mamba_runs(cfg_file)
    scans = ssm.geometry_counts()
    ssm_geometry = {"%dx%d/%d/h%dg%d" % g: c for g, c in scans.items()}
    kernel_ok = set(attn.get("train", {})) == want and bool(scans) and all(
        g[2:] == (cfg_file["mamba_chunk_size"], cfg_file["mamba_n_heads"],
                  cfg_file["mamba_n_groups"]) and c % runs == 0
        for g, c in scans.items())
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

    remat_plan = engine.remat_plan()
    docs_per_row = statistics.fmean(gauge.values) if gauge.values else None

    def summed(key: str, only_traced: bool) -> Dict[str, int]:
        tot: Dict[str, int] = {}
        for x in steps:
            if x["traced"] or not only_traced:
                for g, c in x[key].items():
                    tot[g] = tot.get(g, 0) + c
        return tot

    calls_traced = scan_calls(cfg_file, summed("infer_mbs", True),
                              summed("train_mbs", True), bool(remat_plan))
    notes.append(f"steps={len(steps)} window={elapsed:.3f}s "
                 f"tok_s={thr['tok_s']} mean_tok_s={thr['mean_tok_s']} "
                 f"slow_step_s={thr['slow_step_s']} "
                 f"step_secs={[round(x['secs'], 3) for x in steps]} "
                 f"batch_tokens={batch_tokens} "
                 f"first_importance_weight={first_imp:.4f} attention={attn} "
                 f"blocks={engine.cfg.block_counts()} "
                 f"docs_per_row={docs_per_row} "
                 f"reference={cmp} reference_of={where} "
                 f"window_compiles={window_compiles} "
                 f"window_cache_hits={window_cache_hits} "
                 f"grids={grids} infer_grids={summed('infer_mbs', False)} "
                 f"remat_plan={remat_plan} ssm_geometry={ssm_geometry} "
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
            "n_params": granite_cost.share_params(cfg_file),
            "state_bytes": state_bytes,
            "blocks": engine.cfg.block_counts(),
            # the scans as the program traced them, and those the traced
            # steps ran
            "ssm_geometry": ssm_geometry,
            "granite_scan_calls_traced": calls_traced,
            # the program's gauge, over the window's train batches
            "docs_per_row": docs_per_row,
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
