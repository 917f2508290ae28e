"""Driver ``train_ep``: ``train``'s step — ``PPOActorInterface``
``inference`` then ``train_step`` on packed trajectory batches — for a
sparse-expert model whose experts are spread over the chips of one host
(``allocation_mode`` with an ``e`` factor). One process holds all the
chips.

It is ``drivers/train.py`` where it can be (the sample layout, the packer's
counter, the window and its reduction are imported) and differs in:

 - the model comes to the experiment as a checkpoint's does: a
   ``config.json`` of the configuration file's HF keys is written under
   the run's output directory and ``actor.path`` points at it, so
   ``cli_args.validate_config`` reads the expert count there (the weights
   are still made from ``--seed``, on the mesh, each chip its own share);
 - the reference is the one the configuration file names (``reference``);
 - ``n_params`` is the activated matmul parameters (``moe_cost``);
 - ``correct`` also wants: no (token, expert) pair dropped in any step, the
   expert-parallel path engaged for every packed grid, and the reference
   comparison within the tolerance below;
 - the step's routing counters go into the records for the per-layer
   metrics (``expert_load_ratio``, ``moe_experts_roofline``).
"""

from __future__ import annotations

import dataclasses
import importlib
import json
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
from benchmark import harness, moe_cost, readers, traffic  # noqa: E402
from benchmark.drivers.train import (  # noqa: E402
    REFERENCE_TOKENS, PackCounter, to_sample)

# Engine logprobs (bf16 compute, sorted grouped GEMMs over four chips)
# against reference_olmoe (float32, every expert on every token), over the
# first trajectory's 1009 tokens. Measured on the chip on seven seeds
# (PERF.md section 2, PR 26): 0.042-0.057 nat at the worst token, 0.0068-
# 0.0081 on average. The mean is the sharp one; its limit is 1.36 x the
# largest measured. The same reference moved by 0.0173 on average when
# every token's smallest gate was left out (one pair of eight dropped) and
# by 0.0088 with the experts' inputs and weights rounded to float8_e4m3 —
# on top of the engine's own error either is over the limit (the second
# narrowly: 0.0111-0.0120 in quadrature). One lost pair in a whole batch
# moves no mean: ``moe_dropped_frac`` and the routed-row count are checked
# for that.
LOGPROB_MAX_ERR = 0.1
LOGPROB_MEAN_ERR = 0.011


def build_experiment(spec: Dict[str, Any]):
    """``driverlib.build_experiment`` with the model given as a checkpoint
    directory that holds the configuration's ``config.json`` (and no
    weights: the driver makes those from the seed)."""
    from areal_tpu.api import cli_args as CA
    from areal_tpu.experiments import make_experiment_cls

    t = spec["traffic"]
    ckpt = os.path.join(spec["out"], "ckpt")
    os.makedirs(ckpt, exist_ok=True)
    with open(os.path.join(ckpt, "config.json"), "w") as f:
        json.dump({k: v for k, v in spec["config"].items()
                   if not isinstance(v, (list, dict))}, f, indent=1)
    exp = CA.apply_overrides(make_experiment_cls(t["experiment"])(), [
        "experiment_name=bench", f"trial_name={spec['workload']}",
        f"cluster.fileroot={spec['out']}/exps", "mock_tokenizer=true",
        f"n_gpus_per_node={spec['cell']['chips']}",
        f"actor.path={ckpt}", *t["overrides"],
    ])
    CA.validate_config(exp)
    return exp


def build_model(spec: Dict[str, Any], exp):
    """(model, interfaces, trainer config) as ``TrainerWorker.setup``
    builds them; the weights are made from the seed directly in the
    layout the engine keeps them in, each chip making its own share."""
    import jax

    import areal_tpu.algorithms  # noqa: F401 — registers the interfaces
    import areal_tpu.backend.jax_train  # noqa: F401 — registers the backend
    from areal_tpu.api.model import Model, make_backend, make_interface
    from areal_tpu.models import transformer
    from areal_tpu.parallel import mesh as pmesh
    from areal_tpu.parallel import sharding as psh
    from benchmark import weights

    tcfg = exp.build_trainer_config(async_mode=True)
    rc = tcfg.models["actor"]
    model_cfg = dataclasses.replace(
        weights.model_config(spec["config"]), dtype="float32")
    backend = make_backend(rc.backend, **{"train": rc.train,
                                          **rc.backend_args})
    backend.mesh = pmesh.make_mesh(
        pmesh.ParallelSpec.parse(rc.backend_args["parallel_spec"]))
    shardings = psh.named_shardings(
        backend.mesh, psh.param_partition_specs(model_cfg))
    key = jax.random.fold_in(jax.random.PRNGKey(int(spec["seed"])), 0)
    params = jax.block_until_ready(jax.jit(
        lambda k: transformer.init_params(model_cfg, k),
        out_shardings=shardings)(key))
    model = backend.initialize(Model("actor", (model_cfg, params)),
                               tcfg.ft_spec)
    ifaces = {
        name: make_interface(tcfg.mfcs[name].interface,
                             **tcfg.mfcs[name].interface_args)
        for name in ("actor_inf", "actor_train")
    }
    return model, ifaces, tcfg


def compare_logprobs(got: np.ndarray, ref: np.ndarray) -> Dict[str, Any]:
    cmp = dl.compare_logprobs(got, ref)
    cmp["ok"] = bool(np.isfinite(np.asarray(got, np.float64)).all()
                     and cmp["max_err"] <= LOGPROB_MAX_ERR
                     and cmp["mean_err"] <= LOGPROB_MEAN_ERR)
    return cmp


def main() -> int:
    spec = dl.load_spec()
    t, out = spec["traffic"], spec["out"]
    split: Dict[str, float] = {"imports_s": time.time() - spec["t0"]}
    t_mark = time.time()
    import jax

    from areal_tpu.base.compile_watch import enable_compilation_cache
    from areal_tpu.models import moe
    from areal_tpu.ops import attention

    enable_compilation_cache()
    device = dl.require_device(spec)
    exp = build_experiment(spec)
    model, ifaces, tcfg = build_model(spec, exp)
    engine = model.module
    split["weights_backend_s"] = time.time() - t_mark
    inf_spec, train_spec = exp.actor_inf.mb_spec, exp.actor_train.mb_spec
    packs = PackCounter(engine)
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

    # Set-up, as in ``train``: behaviour logprobs by the same engine, then
    # every batch warmed once, then one more forward of each (the engine
    # compiles its forward of every grid again once the optimizer has
    # stepped).
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
    grids = dict(packs.shapes)  # every packed grid of the mix: n_mbs x R x L
    packs.reset()

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
        shapes_before = dict(packs.shapes)
        stats.append(step(samples[i % n]))
        if traced and i + 1 == 2 * n:
            trace.stop()
        now = time.monotonic() - t0
        # micro-batches of this step: the train split and the inference
        # split pack alike, PackCounter counts the train one
        n_mbs = sum(int(k.split("x")[0]) * (c - shapes_before.get(k, 0))
                    for k, c in packs.shapes.items())
        steps.append({"batch": i % n, "secs": now - elapsed,
                      "traced": traced, "n_mbs": n_mbs})
        elapsed = now
    if trace:
        trace.stop()
    memory_peak = dl.memory_peak_bytes()  # the fullest of the chips
    per_device_peak = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                       for d in jax.local_devices()]
    cache_end = dl.cache_counts()
    warm = split["compile_cache_after_warmup"]
    window_compiles = cache_end.get("misses", 0) - warm.get("misses", 0)
    window_cache_hits = cache_end.get("hits", 0) - warm.get("hits", 0)
    thr = readers.window_throughput(steps, batch_tokens)

    notes: List[str] = []
    bad_steps = sum(
        not (math.isfinite(st["actor_loss"]) and math.isfinite(st["grad_norm"])
             and st["grad_norm"] > 0) for st in stats)
    first_imp = warm_stats[0]["importance_weight"]
    attn = attention.dispatch_counts()
    want = {"tpu": "pallas"}.get(spec["platform"], "reference")
    kernel_ok = set(attn.get("train", {})) == {want}
    # the expert layer: nothing dropped in any step, and the
    # expert-parallel path engaged for every grid the packer made
    dropped = [st.get("moe_dropped_frac") for st in warm_stats + stats]
    dropless = all(d == 0.0 for d in dropped)
    ep_engaged = {g: int(moe.ep_eligible(
        engine.mesh, engine.cfg.moe, *(int(x) for x in g.split("x")[1:])))
        for g in grids}
    ep_ok = bool(ep_engaged) and all(ep_engaged.values())
    s0 = samples[0]
    n0 = int(s0.total_lens("packed_input_ids")[0])
    n_ref = min(n0, REFERENCE_TOKENS)
    toks0 = np.asarray(s0.data["packed_input_ids"][:n_ref])
    one = s0.select_idx([0])
    got = ifaces["actor_inf"].inference(model, one, inf_spec).data[
        "prox_logprobs"][1:n_ref]
    reference = importlib.import_module(
        "benchmark." + spec["config"]["reference"])
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(reference.token_logprobs(
            engine.params, spec["config"], toks0))
    cmp = compare_logprobs(got, ref)
    correct = (bad_steps == 0 and abs(first_imp - 1.0) < 0.05 and kernel_ok
               and dropless and ep_ok and cmp["ok"] and window_compiles == 0
               and thr["tok_s"] is not None)
    load_ratio = [st["moe_expert_load_ratio"] for st in stats]
    notes.append(f"steps={len(steps)} window={elapsed:.3f}s "
                 f"tok_s={thr['tok_s']} mean_tok_s={thr['mean_tok_s']} "
                 f"slow_step_s={thr['slow_step_s']} "
                 f"step_secs={[round(x['secs'], 3) for x in steps]} "
                 f"batch_tokens={batch_tokens} "
                 f"first_importance_weight={first_imp:.4f} attention={attn} "
                 f"moe_dropped_frac_max={max(dropped)} "
                 f"moe_ep_engaged={ep_engaged} "
                 f"moe_expert_load_ratio={statistics.fmean(load_ratio):.4f} "
                 f"reference={cmp} window_compiles={window_compiles} "
                 f"window_cache_hits={window_cache_hits} "
                 f"grids={grids} hbm_peak_by_chip={per_device_peak} "
                 f"setup_split={split}")

    red = trace.reduce() if trace else {}
    if red:
        # ``trace_reduce`` gives seconds per chip (the mean over the
        # device planes) but counts an op's calls over all of them; the
        # flash readers divide the one by the other, so hand them calls
        # per chip too.
        red["op_calls"] = {k: v / len(red["busy_s_per_chip"])
                           for k, v in red["op_calls"].items()}
    traced_steps = [(st, x) for st, x in zip(stats, steps) if x["traced"]]
    records = {
        "device": device, "chips": int(spec["cell"]["chips"]),
        "window_s": elapsed, "config": spec["config"],
        "counters": {
            "steps": len(steps), "batch_tokens": batch_tokens, **thr,
            "pack_real_tokens": packs.real,
            "pack_padded_tokens": packs.padded,
            "pack_shapes": packs.shapes,
            "window_compiles": window_compiles,
            "window_cache_hits": window_cache_hits,
            "n_params": moe_cost.activated_matmul_params(spec["config"]),
            "moe_expert_load_ratio": statistics.fmean(load_ratio),
            "moe_dropped_frac_max": max(dropped),
            # of the traced steps: (token, expert) rows routed per layer,
            # and micro-batches (each one grouped-GEMM call a layer a pass)
            "moe_routed_rows_traced": sum(
                st["moe_routed_rows"] for st, _ in traced_steps),
            "moe_mbs_traced": sum(x["n_mbs"] for _, x in traced_steps),
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
