"""What the ``main`` of a one-chip SHARE driver does whatever the model
family: build the trainer's model from the seed, hook the packer, warm
every batch, run ``PPOActorInterface.inference`` + ``train_step`` for the
window, and assemble the checks, counters and result that do not depend
on the family — one chip's share of a model whose expert layers are shared
by an expert-parallel group, run with no other chip.

``drivers/train_lfm2.py`` is the first caller and keeps only what is its
family's: the kernels it wants traced, the comparison with its reference
and the calls its rooflines count. The share drivers before it
(``train_share``, ``train_hybrid``, ``train_afmoe``, ``train_sambay``,
``train_granite``, ``train_qwen3_next``) each carry these lines in their
own ``main``; they are accepted files, so folding them onto this one is a
``benchmark`` PR's.
"""

from __future__ import annotations

import collections
import dataclasses
import math
import os
import statistics
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from benchmark import driverlib as dl
from benchmark import harness, readers, traffic
from benchmark.drivers.train import PackCounter, to_sample
from benchmark.drivers.train_ep import build_experiment
from benchmark.drivers.train_granite import Gauge
from benchmark.drivers.train_hybrid import Placements


class Layouts:
    """Every micro-batch the packer made, in order: ``(pass, "RxL", its
    documents' lengths)`` with ``pass`` ``"infer"`` inside the engine's
    ``forward`` (the inference pass) and ``"train"`` elsewhere — what an
    attention roofline counts a DOCUMENT at a time, and the grids of both
    passes. Read around the packer, as ``train_share.InferGrids`` reads
    the inference pass's grids."""

    def __init__(self, engine):
        from areal_tpu.backend import microbatch as mbu

        self.log: List[Tuple[str, str, Tuple[int, ...]]] = []
        self._inside = False
        inner_forward = engine.forward
        inner_split = mbu.split_into_microbatches

        def forward(*a, **kw):
            self._inside = True
            try:
                return inner_forward(*a, **kw)
            finally:
                self._inside = False

        def split(*a, **kw):
            mbs = inner_split(*a, **kw)
            for mb in mbs:
                self.log.append((
                    "infer" if self._inside else "train",
                    "%dx%d" % tuple(mb.layout.shape),
                    tuple(int(n) for n in mb.layout.seqlens)))
            return mbs

        engine.forward = forward
        mbu.split_into_microbatches = split


@dataclasses.dataclass
class Share:
    """A run's state between :func:`set_up`, :func:`measure` and
    :func:`result`."""

    spec: Dict[str, Any]
    exp: Any
    model: Any
    ifaces: Dict[str, Any]
    device: Dict[str, Any]
    split: Dict[str, Any]  # the set-up's seconds, by part
    state_bytes: Optional[int]
    packs: PackCounter
    placements: Placements
    layouts: Layouts
    gauges: Dict[str, Gauge]
    samples: List[Any] = dataclasses.field(default_factory=list)
    warm_stats: List[Dict[str, float]] = dataclasses.field(
        default_factory=list)
    grids: Dict[str, int] = dataclasses.field(default_factory=dict)
    every_grid: set = dataclasses.field(default_factory=set)
    # the window (:func:`measure`)
    stats: List[Dict[str, float]] = dataclasses.field(default_factory=list)
    steps: List[Dict[str, Any]] = dataclasses.field(default_factory=list)
    trace: Optional[dl.TraceWindow] = None
    window_start: float = 0.0
    elapsed: float = 0.0
    memory_peak: Optional[int] = None
    window_compiles: int = 0
    window_cache_hits: int = 0
    thr: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def engine(self):
        return self.model.module

    @property
    def inf_spec(self):
        return self.exp.actor_inf.mb_spec

    @property
    def batch_tokens(self) -> List[int]:
        return [int(sum(s.total_lens("packed_input_ids")))
                for s in self.samples]

    def step(self, sample) -> Dict[str, float]:
        """One trainer step of the async recipe; ends on the host with the
        step's statistics, so the device has finished."""
        with dl.span("train/actor_inf"):
            sample.update_(self.ifaces["actor_inf"].inference(
                self.model, sample, self.inf_spec))
        with dl.span("train/actor_train"):
            return self.ifaces["actor_train"].train_step(
                self.model, sample, self.exp.actor_train.mb_spec)


def set_up(spec: Dict[str, Any], build_model: Callable,
           gauge_names: Sequence[str]) -> Share:
    """The model, the hooks around the packer and the warmed batches, as
    in ``train_share``: behaviour logprobs by the same engine, then every
    batch warmed once, then one more forward of each. Call it first in
    ``main`` (``imports_s`` ends here)."""
    split: Dict[str, Any] = {"imports_s": time.time() - spec["t0"]}
    t_mark = time.time()
    import jax

    from areal_tpu.base.compile_watch import enable_compilation_cache

    enable_compilation_cache()
    device = dl.require_device(spec)
    exp = build_experiment(spec)
    model, ifaces, _ = build_model(spec, exp)
    engine = model.module
    split["weights_backend_s"] = time.time() - t_mark
    state_bytes = (jax.local_devices()[0].memory_stats() or {}).get(
        "bytes_in_use")
    share = Share(
        spec=spec, exp=exp, model=model, ifaces=ifaces, device=device,
        split=split, state_bytes=state_bytes, packs=PackCounter(engine),
        placements=Placements(engine), layouts=Layouts(engine),
        gauges={name: Gauge(name) for name in gauge_names})
    dl.wrap_span(engine, "train_uniform", "train/dispatch_minibatch")
    dl.wrap_span(engine, "run_prep", "train/advantage_prep")
    dl.wrap_span(engine, "forward", "train/inference_forward")

    t = spec["traffic"]
    raw = traffic.make_train_batches(
        t["shape"], t["n_batches"], exp.dataset.train_bs_n_seqs,
        exp.group_size, spec["seed"], spec["config"]["vocab_size"])
    t_mark = time.time()
    for i, b in enumerate(raw):
        b["packed_logprobs"] = np.zeros(len(b["packed_input_ids"]), np.float32)
        s = to_sample(b, f"b{i}")
        prox = ifaces["actor_inf"].inference(model, s, share.inf_spec)
        s.data["packed_logprobs"] = (
            prox.data["prox_logprobs"] * (1 - b["prompt_mask"])
        ).astype(np.float32)
        share.samples.append(s)
    for s in share.samples:
        share.warm_stats.append(share.step(s))
    for s in share.samples:
        ifaces["actor_inf"].inference(model, s, share.inf_spec)
    split["warmup_s"] = time.time() - t_mark
    split["compile_cache_after_warmup"] = dl.cache_counts()
    # every train grid of the mix, n_mbs x R x L, and every grid of either
    # pass, R x L
    share.grids = dict(share.packs.shapes)
    share.every_grid = {key for _, key, _ in share.layouts.log}
    share.packs.reset()
    for g in share.gauges.values():
        g.values.clear()
    return share


def measure(share: Share) -> None:
    """The window: steps over the warmed batches in turn for
    ``spec["seconds"]``, the second lap traced where the run is traced;
    each step keeps the micro-batches the packer made in it."""
    spec, n = share.spec, len(share.samples)
    share.trace = dl.TraceWindow(spec["out"]) if spec["trace"] else None
    trace, steps, log = share.trace, share.steps, share.layouts.log
    share.window_start = time.time()
    t0 = time.monotonic()
    elapsed = 0.0
    while elapsed < spec["seconds"]:
        i = len(steps)
        if trace and i == n:
            trace.start()
        traced = bool(trace and trace.on)
        logged = len(log)
        share.stats.append(share.step(share.samples[i % n]))
        if traced and i + 1 == 2 * n:
            trace.stop()
        now = time.monotonic() - t0
        steps.append({"batch": i % n, "secs": now - elapsed, "traced": traced,
                      "layouts": log[logged:]})
        elapsed = now
    if trace:
        trace.stop()
    share.elapsed = elapsed
    share.memory_peak = dl.memory_peak_bytes()  # before any reference runs
    cache_end = dl.cache_counts()
    warm = share.split["compile_cache_after_warmup"]
    share.window_compiles = cache_end.get("misses", 0) - warm.get("misses", 0)
    share.window_cache_hits = cache_end.get("hits", 0) - warm.get("hits", 0)
    share.thr = readers.window_throughput(steps, share.batch_tokens)


def steps_sound(share: Share) -> Dict[str, Any]:
    """Finite loss and grad norm at every step, the first importance
    weight within 0.05 of 1, 0 compiles in the window, a rate — and every
    step trained on EVERY generated token of its batch in the recipe's
    optimizer steps (a micro-batch or a minibatch left out reads fewer)."""
    bad = sum(
        not (math.isfinite(st["actor_loss"]) and math.isfinite(st["grad_norm"])
             and st["grad_norm"] > 0) for st in share.stats)
    first_imp = share.warm_stats[0]["importance_weight"]
    generated = [float(np.sum(np.asarray(s.data["prompt_mask"]) == 0))
                 for s in share.samples]
    n_opt = float(share.ifaces["actor_train"].hp.ppo_n_minibatches)
    whole = all(st["n_action_tokens"] == generated[x["batch"]]
                and st["n_ppo_steps"] == n_opt
                for st, x in zip(share.stats, share.steps))
    return {"bad_steps": bad, "first_importance_weight": first_imp,
            "every_token_trained": bool(whole),
            "ok": bool(bad == 0 and abs(first_imp - 1.0) < 0.05 and whole
                       and share.window_compiles == 0
                       and share.thr["tok_s"] is not None)}


def routing(share: Share, band: Tuple[float, float]) -> Dict[str, Any]:
    """The share of the expert layer over the warm-up's and the window's
    steps: nothing dropped in any, no bounded pass on the whole buffer, and
    the (token, expert) pairs that landed on this chip over those routed
    within ``band``."""
    every = share.warm_stats + share.stats
    dropped = [st.get("moe_dropped_frac") for st in every]
    local = [st.get("moe_local_rows", float("nan")) / st["moe_routed_rows"]
             for st in every]
    full_passes = sum(st.get("moe_full_passes", 0.0) for st in every)
    return {"dropped_max": max(dropped), "local": (min(local), max(local)),
            "full_passes": full_passes,
            "ok": bool(all(d == 0.0 for d in dropped)
                       and all(band[0] <= x <= band[1] for x in local)
                       and full_passes == 0)}


def traced_layouts(share: Share) -> List[Tuple[str, str, Tuple[int, ...]]]:
    """The micro-batches of the traced steps, in order."""
    return [lay for x in share.steps if x["traced"] for lay in x["layouts"]]


def result(share: Share, correct: bool, sound: Dict[str, Any],
           routed: Dict[str, Any], n_params: int, counters: Dict[str, Any],
           note: str) -> Dict[str, Any]:
    """Writes ``result.json``: the family's ``counters`` and ``note``
    beside the ones every share driver reports."""
    spec, stats, steps = share.spec, share.stats, share.steps
    engine, thr = share.engine, share.thr
    gauge = {name: statistics.fmean(g.values) if g.values else None
             for name, g in share.gauges.items()}
    traced = [(st, x) for st, x in zip(stats, steps) if x["traced"]]
    load_ratio = statistics.fmean(st["moe_expert_load_ratio"] for st in stats)
    infer_grids = dict(collections.Counter(
        key for x in steps for which, key, _ in x["layouts"]
        if which == "infer"))
    remat_plan = engine.remat_plan()
    notes = [f"steps={len(steps)} window={share.elapsed:.3f}s "
             f"tok_s={thr['tok_s']} mean_tok_s={thr['mean_tok_s']} "
             f"slow_step_s={thr['slow_step_s']} "
             f"step_secs={[round(x['secs'], 3) for x in steps]} "
             f"batch_tokens={share.batch_tokens} "
             f"first_importance_weight="
             f"{sound['first_importance_weight']:.4f} "
             f"every_token_trained={sound['every_token_trained']} "
             f"blocks={engine.cfg.block_counts()} gauges={gauge} "
             f"moe_dropped_frac_max={routed['dropped_max']} "
             f"moe_local_share={routed['local'][0]:.4f}.."
             f"{routed['local'][1]:.4f} "
             f"moe_full_passes={routed['full_passes']} "
             f"moe_expert_load_ratio={load_ratio:.4f} {note} "
             f"window_compiles={share.window_compiles} "
             f"window_cache_hits={share.window_cache_hits} "
             f"grids={share.grids} infer_grids={infer_grids} "
             f"remat_plan={remat_plan} "
             f"state_bytes={share.state_bytes} hbm_peak={share.memory_peak} "
             f"setup_split={share.split}"]
    red = share.trace.reduce() if share.trace else {}
    chips = int(spec["cell"]["chips"])
    records = {
        "device": share.device, "chips": chips,
        "window_s": share.elapsed, "config": spec["config"],
        "counters": {
            "steps": len(steps), "batch_tokens": share.batch_tokens, **thr,
            "pack_real_tokens": share.packs.real,
            "pack_padded_tokens": share.packs.padded,
            "pack_shapes": share.packs.shapes,
            "window_compiles": share.window_compiles,
            "window_cache_hits": share.window_cache_hits,
            "n_params": n_params,
            "state_bytes": share.state_bytes,
            "blocks": engine.cfg.block_counts(),
            **counters,
            # the program's gauges, over the window's train batches
            **{name.split("/", 1)[1]: v for name, v in gauge.items()},
            "moe_expert_load_ratio": load_ratio,
            "moe_dropped_frac_max": routed["dropped_max"],
            "moe_full_passes": routed["full_passes"],
            # (token, expert) pairs per layer over the window's steps:
            # routed over all experts, and landed on the held ones
            "moe_routed_rows": sum(st["moe_routed_rows"] for st in stats),
            "moe_local_rows": sum(st.get("moe_local_rows", 0.0)
                                  for st in stats) if all(
                "moe_local_rows" in st for st in stats) else None,
            # of the traced steps, and their micro-batches (each one
            # grouped-GEMM call a layer a pass)
            "moe_local_rows_traced": sum(
                st.get("moe_local_rows", 0.0) for st, _ in traced),
            "moe_mbs_traced": sum(
                sum(which == "train" for which, _, _ in x["layouts"])
                for _, x in traced),
        },
        "memory_peak_bytes": share.memory_peak,
        "trace": red, "setup_split": share.split,
    }
    out = {
        "correct": bool(correct), "attempted": len(stats),
        "failed": int(sound["bad_steps"]),
        "end_to_end": {
            "train_tok_s_chip": (thr["tok_s"] or 0.0) / chips,
            "setup_s": share.window_start - spec["t0"],
        },
        "device": {**share.device, "memory_peak_bytes": share.memory_peak,
                   **({"busy_s": red["busy_s"], "window_s": red["window_s"]}
                      if red else {})},
        "breakdown": dl.breakdown(red),
        "records": records, "notes": notes,
    }
    harness.write_json(os.path.join(spec["out"], "result.json"), out)
    return out
