"""Driver ``train_share``: ``train_ep``'s step — ``PPOActorInterface``
``inference`` then ``train_step`` on packed trajectory batches — for ONE
chip's share of a sparse-expert model whose layers are shared by an
expert-parallel group: the configuration holds ``num_experts`` of the
``num_routed_experts`` the router scores and a slice of the vocabulary,
and the program runs them with no other chip and nothing standing in for
one (``areal_tpu/models/moe.py``, a share).

It is ``drivers/train_ep.py`` where it can be (the experiment with its
``config.json`` is imported from there; the sample layout and the packer's
counter from ``drivers/train.py``) and differs in:

 - the weights are ``drivers/train.py``'s (the program's own init from
   ``--seed``, router included, on the one chip) with the embedding drawn
   at unit scale (``EMBED_SCALE``): see there;
 - one chip, no ``ep`` axis: ``correct`` does not ask for the
   expert-parallel path;
 - ``correct`` also wants: the train step's attention traced to the two
   kernels (flash on the full layers, the windowed kernel on the sliding
   ones) and to nothing else; no (token, expert) pair dropped in any
   step; the pairs that landed on this chip between 20 and 30 % of those
   routed (16 of 64 experts, a random router: 25 %); and the engine's
   logprobs of the first trajectory's first ``REFERENCE_TOKENS`` tokens —
   three quarters of them further in than the window — against the
   configuration's reference within the tolerance below;
 - ``n_params`` is the share's (``window_trace.share_params``);
 - the windowed kernel's trace-time count and the calls the traced steps
   ran, and the share's routing counters, go into the records for the
   per-layer metrics ``window_*`` and ``share_*``.
"""

from __future__ import annotations

import importlib
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
from benchmark import harness, readers, traffic, window_trace  # noqa: E402
from benchmark.drivers.train import PackCounter, to_sample  # noqa: E402
from benchmark.drivers.train_ep import build_experiment  # noqa: E402

REFERENCE_TOKENS = 4096  # of the first trajectory; the window is 1024

# Engine logprobs (bf16 compute, the two attention kernels, sorted grouped
# GEMMs over the 16 held experts) against reference_mellum2 (float32,
# every held expert on every token), over the first trajectory's first
# 4096 tokens. SET FROM the chip (my chip runs, PR 32; PERF.md section 2),
# eight seeds: 0.0056-0.0059 nat on average (the sharp one: its limit is
# 1.27 x the largest measured), 0.075-0.115 at the worst token (limit
# 1.7 x). What fails them, the same engine against a WRONG reference
# (benchmark/check_limits_mellum2.py, two seeds; mean / max): the window
# left off the sliding layers 0.077-0.078 / 0.51-0.53; plain RoPE in place
# of YaRN on the full layer 0.052 / 0.30-0.35; the gates not renormalised
# 0.055 / 0.34-0.35; the experts' inputs and weights rounded to
# float8_e4m3, the nearest precision below the configuration's bfloat16,
# 0.0090-0.0093 / 0.08-0.11 — over the mean limit and not the max one.
LOGPROB_MAX_ERR = 0.2
LOGPROB_MEAN_ERR = 0.0075
# (token, expert) pairs on this chip over pairs routed: 16 / 64 held.
LOCAL_SHARE = (0.20, 0.30)
# The program's init draws every matrix at 0.02, the embedding too. A token
# then enters the residual stream 60 x smaller than what the first
# attention layer adds to it (v is of order 1 behind the norm, its running
# mean over the window of order 1/sqrt(t) x 1.3 behind Wo) — and that mean
# is the SAME direction for neighbouring tokens, which a random router
# turns into a skew: busiest expert 4.4-4.8 x the mean, the 16 held
# experts' share 0.23-0.31 by seed, and with it the grouped GEMMs' work
# and the throughput (7 seeds: spread 0.62 %, over half the bound; my chip
# runs, PR 32). A model that has been trained routes evenly (its auxiliary
# loss sees to it). Drawn at unit scale the embedding is what the router
# sees, tokens are independent, and the share is 0.25 +- 0.01 on every seed.
EMBED_SCALE = 50.0


def compare_logprobs(got: np.ndarray, ref: np.ndarray) -> Dict[str, Any]:
    cmp = dl.compare_logprobs(got, ref)
    cmp["ok"] = bool(np.isfinite(np.asarray(got, np.float64)).all()
                     and cmp["max_err"] <= LOGPROB_MAX_ERR
                     and cmp["mean_err"] <= LOGPROB_MEAN_ERR)
    return cmp


def build_model(spec: Dict[str, Any], exp):
    """``drivers/train.build_model`` with the embedding at unit scale."""
    import areal_tpu.algorithms  # noqa: F401 — registers the interfaces
    import areal_tpu.backend.jax_train  # noqa: F401 — registers the backend
    from areal_tpu.api.model import Model, make_backend, make_interface
    from benchmark import weights

    tcfg = exp.build_trainer_config(async_mode=True)
    rc = tcfg.models["actor"]
    model_cfg = weights.model_config(spec["config"])
    params = weights.make_params(model_cfg, spec["seed"])
    params = {**params, "embedding": params["embedding"] * EMBED_SCALE}
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


class InferGrids:
    """The packed ``[R, L]`` grid of every micro-batch the engine's
    ``forward`` (the inference pass) splits its input into: it packs the
    whole batch, the train step packs each minibatch, so the two need not
    make the same grids. Counted around the packer while ``forward`` runs."""

    def __init__(self, engine):
        from areal_tpu.backend import microbatch as mbu

        self.grids: Dict[str, int] = {}
        self._inside = False
        inner_forward, inner_split = engine.forward, mbu.split_into_microbatches

        def forward(*a, **kw):
            self._inside = True
            try:
                return inner_forward(*a, **kw)
            finally:
                self._inside = False

        def split(*a, **kw):
            mbs = inner_split(*a, **kw)
            if self._inside:
                for mb in mbs:
                    key = "%dx%d" % tuple(mb.layout.shape)
                    self.grids[key] = self.grids.get(key, 0) + 1
            return mbs

        engine.forward = forward
        mbu.split_into_microbatches = split


def reference_prefix(ifaces, model, inf_spec, sample):
    """(engine logprobs, tokens) of the first trajectory's first
    ``REFERENCE_TOKENS`` tokens — a causal prefix stands alone."""
    n0 = int(sample.total_lens("packed_input_ids")[0])
    n_ref = min(n0, REFERENCE_TOKENS)
    toks = np.asarray(sample.data["packed_input_ids"][:n_ref])
    got = ifaces["actor_inf"].inference(
        model, sample.select_idx([0]), inf_spec).data["prox_logprobs"][1:n_ref]
    return got, toks


def reference_logprobs(params, cfg_file: Dict[str, Any], toks) -> np.ndarray:
    import jax

    reference = importlib.import_module("benchmark." + cfg_file["reference"])
    with jax.default_matmul_precision("highest"):
        return np.asarray(reference.token_logprobs(params, cfg_file, toks))


def window_calls(program_geometry: Dict, sliding_layers: int,
                 infer_grids: Dict[str, int], train_grids: Dict[str, int],
                 remat_plan: Dict[str, Dict[str, Any]]) -> List[Dict[str, Any]]:
    """The windowed kernel's calls in some steps, for the roofline: each
    micro-batch of a grid ``RxL`` runs the kernel once a sliding layer a
    pass — forward in the inference pass; in the train pass forward (twice
    where the grid's grad program keeps nothing of the kernel and re-runs
    it) and backward. Tile and window of a row length are the program's
    own (its trace-time count, keyed by length)."""
    by_len = {}
    for geoms in program_geometry.values():
        for (n, _, tile, window) in geoms:
            by_len[n] = (tile, window)
    calls = []
    for grids, train in ((infer_grids, False), (train_grids, True)):
        for key, n_mbs in grids.items():
            R, L = (int(x) for x in key.split("x"))
            if L not in by_len:
                continue
            tile, window = by_len[L]
            refwd = train and remat_plan.get(key, {}).get("entry") == "full"
            n = n_mbs * sliding_layers
            calls.append({"rows": R, "length": L, "tile": tile,
                          "window": window,
                          "fwd": n * (2 if refwd else 1),
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

    # Set-up, as in ``train_ep``: behaviour logprobs by the same engine,
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

    notes: List[str] = []
    bad_steps = sum(
        not (math.isfinite(st["actor_loss"]) and math.isfinite(st["grad_norm"])
             and st["grad_norm"] > 0) for st in stats)
    first_imp = warm_stats[0]["importance_weight"]
    attn = attention.dispatch_counts()
    want = ({"pallas", "window"} if spec["platform"] == "tpu"
            else {"reference"})
    kernel_ok = set(attn.get("train", {})) == want
    # the share of the expert layer: nothing dropped in any step, and about
    # a quarter of the routed pairs landed on the held experts
    every = warm_stats + stats
    dropped = [st.get("moe_dropped_frac") for st in every]
    dropless = all(d == 0.0 for d in dropped)
    local = [st.get("moe_local_rows", float("nan")) / st["moe_routed_rows"]
             for st in every]
    share_ok = all(LOCAL_SHARE[0] <= x <= LOCAL_SHARE[1] for x in local)
    got, toks0 = reference_prefix(ifaces, model, inf_spec, samples[0])
    cmp = compare_logprobs(
        got, reference_logprobs(engine.params, spec["config"], toks0))
    correct = (bad_steps == 0 and abs(first_imp - 1.0) < 0.05 and kernel_ok
               and dropless and share_ok and cmp["ok"]
               and window_compiles == 0 and thr["tok_s"] is not None)

    # the windowed kernel: the program's trace-time count (per compiled
    # program and call), and the calls the traced steps ran
    geometry: Dict[str, Dict] = {}
    try:
        from areal_tpu.ops.pallas import window_attention as wa

        geometry = wa.geometry_counts()
    except ImportError:  # a program without the kernel
        pass
    remat_plan = engine.remat_plan()
    sliding = sum(x == "sliding_attention" for x in
                  spec["config"]["layer_types"])

    def summed(key: str, only_traced: bool) -> Dict[str, int]:
        tot: Dict[str, int] = {}
        for x in steps:
            if x["traced"] or not only_traced:
                for g, c in x[key].items():
                    tot[g] = tot.get(g, 0) + c
        return tot

    calls_traced = window_calls(
        geometry, sliding, summed("infer_mbs", True),
        summed("train_mbs", True), remat_plan)
    traced_steps = [(st, x) for st, x in zip(stats, steps) if x["traced"]]
    geometry_keys = {label: {"%d>%d/%d/w%d" % g: c for g, c in geoms.items()}
                     for label, geoms in geometry.items()}
    load_ratio = [st["moe_expert_load_ratio"] for st in stats]
    notes.append(f"steps={len(steps)} window={elapsed:.3f}s "
                 f"tok_s={thr['tok_s']} mean_tok_s={thr['mean_tok_s']} "
                 f"slow_step_s={thr['slow_step_s']} "
                 f"step_secs={[round(x['secs'], 3) for x in steps]} "
                 f"batch_tokens={batch_tokens} "
                 f"first_importance_weight={first_imp:.4f} attention={attn} "
                 f"moe_dropped_frac_max={max(dropped)} "
                 f"moe_local_share={min(local):.4f}..{max(local):.4f} "
                 f"moe_expert_load_ratio={statistics.fmean(load_ratio):.4f} "
                 f"reference={cmp} window_compiles={window_compiles} "
                 f"window_cache_hits={window_cache_hits} "
                 f"grids={grids} infer_grids={summed('infer_mbs', False)} "
                 f"remat_plan={remat_plan} "
                 f"window_geometry={geometry_keys} "
                 f"state_bytes={state_bytes} hbm_peak={memory_peak} "
                 f"setup_split={split}")

    red = trace.reduce() if trace else {}
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
            "n_params": window_trace.share_params(spec["config"]),
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
            # the train step's windowed calls as the program traced them
            "window_geometry": geometry_keys.get("train", {}),
            "window_calls_traced": calls_traced,
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
