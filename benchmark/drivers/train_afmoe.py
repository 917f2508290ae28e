"""Driver ``train_afmoe``: ``train_share``'s step — ``PPOActorInterface``
``inference`` then ``train_step`` on packed trajectory batches, on ONE
chip's share of a model whose expert layers are shared by an
expert-parallel group — for Trinity-Mini's family (``model_type``
``afmoe``): a leading dense block before expert blocks (128 routed
experts behind a sigmoid router beside a shared expert), gated attention
under sandwich norms, a window of 2048 on four blocks of five and no
position embedding on the full one, in rows of up to 16,384 tokens. The
configuration holds ``num_experts`` of the ``num_routed_experts`` the
router scores and a slice of the vocabulary, and the program runs them
with no other chip and nothing standing in for one.

It is ``drivers/train_share.py`` where it can be (the inference pass's
grid counter, the reference call and the windowed kernel's calls are
imported from there; the experiment from ``drivers/train_ep.py``; the
sample layout and the packer's counter from ``drivers/train.py``) and
differs in its weights (``build_model``: the program's own init from
``--seed`` with ``post_attention_layernorm`` at
``POST_ATTN_NORM_WEIGHT``; no embedding
scale of the driver's own: the family multiplies the embedding by
sqrt(hidden), which brings a 0.02 draw to about unit scale) and in its
limits and checks, which are constants of this file:

 - ``correct`` wants: platform, finite loss and grad-norm every step, the
   first importance weight within 0.05 of 1, 0 compiles in the window, no
   (token, expert) pair dropped in any step; the train step's attention
   traced to the two kernels (flash on the full block, the windowed
   kernel on the sliding ones) and to nothing else; the pairs that landed
   on this chip within ``LOCAL_SHARE`` of those routed (8 of 128 experts
   held); no bounded expert pass on the whole buffer; and the engine's
   logprobs of the first ``REFERENCE_TOKENS`` tokens of the first batch's
   longest trajectory (all of it where it is shorter) — three quarters of
   them further in than the window — against the configuration's
   reference within the tolerances below;
 - ``n_params`` is the share's (``afmoe_trace.share_params``);
 - the windowed kernel's trace-time count and the calls the traced steps
   ran, and the share's routing counters, go into the records for the
   per-layer metrics ``afmoe_*``.
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

from benchmark import afmoe_trace, driverlib as dl  # noqa: E402
from benchmark import harness, readers, traffic  # noqa: E402
from benchmark.drivers.train import PackCounter, to_sample  # noqa: E402
from benchmark.drivers.train_ep import build_experiment  # noqa: E402
from benchmark.drivers.train_share import (  # noqa: E402
    InferGrids, reference_logprobs, window_calls)

REFERENCE_TOKENS = 8192  # of the longest trajectory; the window is 2048

# Engine logprobs (bf16 compute, the two attention kernels, sorted grouped
# GEMMs over the 8 held experts) against reference_afmoe (float32 at
# "highest", every held expert on every token), over the first 8192 tokens
# of the first batch's longest trajectory. SET FROM the chip (my chip
# runs, PR 39; PERF.md section 2 has every seed's reading), eleven seeds:
# 0.01259-0.01372 nat on average (the sharp one: its limit is 1.2 x the
# largest measured); 0.655-0.971 at the worst token — a heavy tail (median
# token 0.0077, the 99th percentile 0.16-0.18, the 99.9th 0.35-0.42):
# where two experts' scores tie at the eighth place, bfloat16 and float32
# choose differently, and one token's routed part changes in one of four
# expert layers; the max limit is 1.65 x the largest (the final tree's six
# seeds then read 0.0123-0.0134 / 0.65-1.03: 1.23 x and 1.56 x). What fails them, the
# same engine against a WRONG reference (benchmark/check_limits_afmoe.py,
# three seeds; mean / max): RoPE on the full layer too 0.0298-0.0313 /
# 0.71-1.11 (1.8 x over the mean limit: one block of five, entering the
# stream at a fifth); the projections' and experts' inputs and weights in
# float8_e4m3, the nearest precision below the configuration's bfloat16,
# 0.1151-0.1175 / 1.01-1.08; the gates not scaled by 2.826 0.115-0.118 /
# 0.84-0.87; no gate 0.163-0.164 / 0.96-1.29; softmax for sigmoid
# 0.172-0.182 / 1.35-1.62; no window 0.235-0.239 / 1.67-1.78, window 1024
# 0.264-0.268 / 1.57-1.77; no post-norms 0.50-0.51 / 2.2-2.5; no shared
# expert 0.73-0.74 / 3.6-3.8; the dense block run as an expert block
# 0.82-0.84 / 3.9-4.7 — every one over the mean limit, the last five over
# the max one too.
LOGPROB_MAX_ERR = 1.6
LOGPROB_MEAN_ERR = 0.0165
# (token, expert) pairs on this chip over pairs routed: 8 / 128 = 0.0625
# under an even router; the band is 0.7 x to 1.35 x the even share, as
# the Nemotron cell's.
LOCAL_SHARE = (0.044, 0.085)
# The program's init draws every norm weight at 1. A random model's
# attention logits are then N(0, 1): a nearly flat softmax over the
# window, so the attention branch returns the window's running MEAN of v —
# one direction that neighbouring tokens share — and the sandwich norm
# behind it brings that direction to unit RMS whatever its size (0.03 of
# |v| before the norm). A random router behind it is skewed: busiest
# expert 4.5-4.7 x the mean on the chip (my chip runs, PR 39, two seeds;
# 8.1 on the CPU at the published widths and 3072 tokens). Two remedies
# were measured (same CPU runs: busiest expert / bf16-against-float32
# mean logprob error; as drawn 8.1 / 0.013): the q/k norm weights at 2
# (logits N(0, 16), a token attends to its own few keys) 2.1 / 0.147 — on
# the chip 1.70-2.00 with the reference error at 0.109-0.126 on all ten
# seeds, ten times the flat softmax's: a sharp softmax under a norm that
# renormalises passes every rounding of q and k on in full — and THAT
# post-norm's weight at 0.5 / 0.3 / 0.2: 4.6 / 3.2 / 2.6 at 0.015-0.016.
# So the driver sets post_attention_layernorm's weight to 0.2: the
# attention branch enters the stream at a fifth of the FFN's, every
# kernel, shape and program is the same.
POST_ATTN_NORM_WEIGHT = 0.2


def compare_logprobs(got: np.ndarray, ref: np.ndarray) -> Dict[str, Any]:
    cmp = dl.compare_logprobs(got, ref)
    cmp["ok"] = bool(np.isfinite(np.asarray(got, np.float64)).all()
                     and cmp["max_err"] <= LOGPROB_MAX_ERR
                     and cmp["mean_err"] <= LOGPROB_MEAN_ERR)
    return cmp


def build_model(spec: Dict[str, Any], exp):
    """``drivers/train.build_model`` with every block's
    ``post_attention_layernorm`` at ``POST_ATTN_NORM_WEIGHT``: see there."""
    import areal_tpu.algorithms  # noqa: F401 — registers the interfaces
    import areal_tpu.backend.jax_train  # noqa: F401 — registers the backend
    from areal_tpu.api.model import Model, make_backend, make_interface
    from benchmark import weights

    tcfg = exp.build_trainer_config(async_mode=True)
    rc = tcfg.models["actor"]
    model_cfg = weights.model_config(spec["config"])
    params = weights.make_params(model_cfg, spec["seed"])
    params = {**params, "layers": {
        kind: {name: w * POST_ATTN_NORM_WEIGHT if name == "ln1_post" else w
               for name, w in stack.items()}
        for kind, stack in params["layers"].items()}}
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


def reference_prefix(ifaces, model, inf_spec, sample):
    """(engine logprobs, tokens) of the first ``REFERENCE_TOKENS`` tokens
    of the sample's longest trajectory — a causal prefix stands alone."""
    lens = [int(x) for x in sample.total_lens("packed_input_ids")]
    i = int(np.argmax(lens))
    start, n_ref = sum(lens[:i]), min(lens[i], REFERENCE_TOKENS)
    toks = np.asarray(
        sample.data["packed_input_ids"][start:start + n_ref])
    got = ifaces["actor_inf"].inference(
        model, sample.select_idx([i]), inf_spec).data["prox_logprobs"][1:n_ref]
    return got, toks


def main() -> int:
    spec = dl.load_spec()
    t, out = spec["traffic"], spec["out"]
    split: Dict[str, Any] = {"imports_s": time.time() - spec["t0"]}
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

    raw = traffic.make_train_batches(
        t["shape"], t["n_batches"], exp.dataset.train_bs_n_seqs,
        exp.group_size, spec["seed"], spec["config"]["vocab_size"])

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

    notes: List[str] = []
    bad_steps = sum(
        not (math.isfinite(st["actor_loss"]) and math.isfinite(st["grad_norm"])
             and st["grad_norm"] > 0) for st in stats)
    first_imp = warm_stats[0]["importance_weight"]
    attn = attention.dispatch_counts()
    want = ({"pallas", "window"} if spec["platform"] == "tpu"
            else {"reference"})
    kernel_ok = set(attn.get("train", {})) == want
    # the share of the expert layers: nothing dropped in any step, about a
    # sixteenth of the routed pairs landed on the held experts, and every
    # bounded pass fitted its rows
    every = warm_stats + stats
    dropped = [st.get("moe_dropped_frac") for st in every]
    dropless = all(d == 0.0 for d in dropped)
    local = [st.get("moe_local_rows", float("nan")) / st["moe_routed_rows"]
             for st in every]
    share_ok = all(LOCAL_SHARE[0] <= x <= LOCAL_SHARE[1] for x in local)
    full_passes = sum(st.get("moe_full_passes", 0.0) for st in every)
    got, toks0 = reference_prefix(ifaces, model, inf_spec, samples[0])
    cmp = compare_logprobs(
        got, reference_logprobs(engine.params, spec["config"], toks0))
    correct = (bad_steps == 0 and abs(first_imp - 1.0) < 0.05 and kernel_ok
               and dropless and share_ok and full_passes == 0 and cmp["ok"]
               and window_compiles == 0 and thr["tok_s"] is not None)

    # the windowed kernel: the program's trace-time count (per compiled
    # program and call), and the calls the traced steps ran
    from areal_tpu.ops.pallas import window_attention as wa

    geometry = wa.geometry_counts()
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
                 f"blocks={engine.cfg.block_counts()} "
                 f"moe_dropped_frac_max={max(dropped)} "
                 f"moe_local_share={min(local):.4f}..{max(local):.4f} "
                 f"moe_full_passes={full_passes} "
                 f"moe_expert_load_ratio={statistics.fmean(load_ratio):.4f} "
                 f"reference={cmp} "
                 f"window_compiles={window_compiles} "
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
            "n_params": afmoe_trace.share_params(spec["config"]),
            "state_bytes": state_bytes,
            "moe_expert_load_ratio": statistics.fmean(load_ratio),
            "moe_dropped_frac_max": max(dropped),
            "moe_full_passes": full_passes,
            # (token, expert) pairs per expert layer over the window's
            # steps: routed over all experts, and landed on the held ones
            "moe_routed_rows": sum(st["moe_routed_rows"] for st in stats),
            "moe_local_rows": sum(st.get("moe_local_rows", 0.0)
                                  for st in stats) if all(
                "moe_local_rows" in st for st in stats) else None,
            # of the traced steps, and their micro-batches (each one
            # grouped-GEMM call an expert layer a pass)
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
