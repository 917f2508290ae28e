"""Driver ``train``: one process that holds the chip(s) and runs the
trainer's per-step work of the async recipe — ``PPOActorInterface``
``inference`` (proximal logprobs) then ``train_step`` — on packed
trajectory batches from the traffic generator.

The backend, the interfaces and the micro-batch specs are the ones
``experiment.build_trainer_config`` gives a trainer worker for the
traffic file's overrides; only the weights differ: made on the device
from ``--seed`` instead of read from a checkpoint through torch.
"""

from __future__ import annotations

import math
import os
import sys
import time
from typing import Any, Dict, List

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import driverlib as dl  # noqa: E402
from benchmark import harness, readers, traffic  # noqa: E402

REFERENCE_TOKENS = 2048  # of the first trajectory, against reference.py


def build_model(spec: Dict[str, Any], exp):
    """(model, inference interface, train interface, trainer config) built
    as ``TrainerWorker.setup`` builds them, weights from the seed."""
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


def to_sample(b: Dict[str, np.ndarray], tag: str):
    from areal_tpu.api.data import SequenceSample

    n = len(b["seqlens"])
    return SequenceSample.from_default(
        ids=[f"{tag}s{i}" for i in range(n)],
        data={
            "packed_input_ids": b["packed_input_ids"],
            "prompt_mask": b["prompt_mask"],
            "packed_logprobs": b["packed_logprobs"],
            "rewards": b["rewards"],
            "seq_no_eos_mask": np.ones(n, np.float32),
            "version_start": np.zeros(n, np.int32),
            "version_end": np.zeros(n, np.int32),
        },
        seqlens=b["seqlens"].tolist(),
        metadata={"group": list(b["group"])},
    )


class PackCounter:
    """Real and padded tokens of every micro-batch split the engine makes,
    counted around ``engine.upload_uniform`` (the packer's one caller on
    the train path) and the grid shapes it chose."""

    def __init__(self, engine):
        self.real = self.padded = 0
        self.shapes: Dict[str, int] = {}
        inner = engine.upload_uniform

        def counted(*a, **kw):
            with dl.span("train/pack_upload"):
                ub = inner(*a, **kw)
            self.real += sum(int(mb.n_tokens) for mb in ub.mbs)
            self.padded += ub.n_mbs * ub.R * ub.L
            key = f"{ub.n_mbs}x{ub.R}x{ub.L}"
            self.shapes[key] = self.shapes.get(key, 0) + 1
            return ub

        engine.upload_uniform = counted

    def reset(self):
        self.real = self.padded = 0
        self.shapes = {}


def main() -> int:
    spec = dl.load_spec()
    t, out = spec["traffic"], spec["out"]
    split: Dict[str, float] = {"imports_s": time.time() - spec["t0"]}
    t_mark = time.time()
    from areal_tpu.base.compile_watch import enable_compilation_cache
    from areal_tpu.ops import attention

    enable_compilation_cache()
    device = dl.require_device(spec)
    exp = dl.build_experiment(spec)
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

    # Set-up: behaviour logprobs by the same engine's inference (what a
    # server on these weights would have sent), then every batch warmed
    # once so that no shape compiles in the window.
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
    # The engine compiles its forward of every grid a second time once the
    # optimizer has stepped (same abstract signature; PERF.md section 6):
    # one more forward of each batch takes that out of the window.
    for s in samples:
        ifaces["actor_inf"].inference(model, s, inf_spec)
    split["warmup_s"] = time.time() - t_mark
    split["compile_cache_after_warmup"] = dl.cache_counts()
    packs.reset()

    # The window: the batches in file order, again and again (the order is
    # part of the mix, not of the seed), until ``--seconds`` have passed.
    # A traced run lets every batch pass once, then traces every batch
    # once; the profiler's start and stop fall into the steps marked
    # ``traced``.
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
        stats.append(step(samples[i % n]))
        if traced and i + 1 == 2 * n:
            trace.stop()
        now = time.monotonic() - t0
        steps.append({"batch": i % n, "secs": now - elapsed,
                      "traced": traced})
        elapsed = now
    if trace:
        trace.stop()
    memory_peak = dl.memory_peak_bytes()  # before the reference's forward
    cache_end = dl.cache_counts()
    warm = split["compile_cache_after_warmup"]
    window_compiles = cache_end.get("misses", 0) - warm.get("misses", 0)
    window_cache_hits = cache_end.get("hits", 0) - warm.get("hits", 0)
    thr = readers.window_throughput(steps, batch_tokens)

    # correct: finite every step, importance weight 1 at the first step
    # after the behaviour logprobs were taken, the kernel in the train
    # step, and the engine's logprobs against the plain reference.
    notes: List[str] = []
    bad_steps = sum(
        not (math.isfinite(st["actor_loss"]) and math.isfinite(st["grad_norm"])
             and st["grad_norm"] > 0) for st in stats)
    first_imp = warm_stats[0]["importance_weight"]
    attn = attention.dispatch_counts()
    want = {"tpu": "pallas"}.get(spec["platform"], "reference")
    kernel_ok = set(attn.get("train", {})) == {want}
    s0 = samples[0]
    n0 = int(s0.total_lens("packed_input_ids")[0])
    # A causal prefix stands alone, and the reference holds a whole
    # [heads, T, T] score matrix in float32: compare REFERENCE_TOKENS.
    n_ref = min(n0, REFERENCE_TOKENS)
    toks0 = np.asarray(s0.data["packed_input_ids"][:n_ref])
    one = s0.select_idx([0])
    got = ifaces["actor_inf"].inference(model, one, inf_spec).data[
        "prox_logprobs"][1:n_ref]
    ref = dl.reference_logprobs(engine.params, spec["config"], toks0)
    cmp = dl.compare_logprobs(got, ref)
    correct = (bad_steps == 0 and abs(first_imp - 1.0) < 0.05 and kernel_ok
               and cmp["ok"] and window_compiles == 0
               and thr["tok_s"] is not None)
    notes.append(f"steps={len(steps)} window={elapsed:.3f}s "
                 f"tok_s={thr['tok_s']} mean_tok_s={thr['mean_tok_s']} "
                 f"slow_step_s={thr['slow_step_s']} "
                 f"step_secs={[round(x['secs'], 3) for x in steps]} "
                 f"batch_tokens={batch_tokens} "
                 f"first_importance_weight={first_imp:.4f} attention={attn} "
                 f"reference={cmp} window_compiles={window_compiles} "
                 f"window_cache_hits={window_cache_hits} "
                 f"shapes={packs.shapes} setup_split={split}")

    red = trace.reduce() if trace else {}
    from benchmark import peaks

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
            "n_params": peaks.param_count(spec["config"]),
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
