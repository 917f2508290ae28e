"""Driver ``train_qwen3_next``: ``train_share``'s step — ``PPOActorInterface``
``inference`` then ``train_step`` on packed trajectory batches, on ONE
chip's share of a model whose expert layers are shared by an
expert-parallel group — for a Qwen3-Next model (``model_type`` qwen3_next):
whole blocks of a Gated DeltaNet linear-attention mixer, 3 to 1 against
gated softmax attention at heads of 256 with partial rotary, every block
with 512 routed experts (10 a token) beside a shared expert behind a
sigmoid gate, under zero-centred norms, in rows of up to 16,384 tokens.
The configuration holds ``num_experts`` of the ``num_routed_experts`` the
router scores, one whole period of the published layers and a slice of the
vocabulary, and the program runs them with no other chip and nothing
standing in for one.

It is the drivers before it where it can be (the model with the embedding
at unit scale, the inference pass's grid counter and the reference call
from ``drivers/train_share.py``; the packer's placements from
``drivers/train_hybrid.py``; the gauge reader from
``drivers/train_granite.py``; the experiment from ``drivers/train_ep.py``;
the sample layout and the packer's counter from ``drivers/train.py``) and
differs in its limits and checks, which are constants of this file:

 - ``correct`` wants: platform, finite loss and grad-norm every step, the
   first importance weight within 0.05 of 1, 0 compiles in the window, no
   (token, expert) pair dropped in any step, the pairs that landed on this
   chip within ``LOCAL_SHARE`` of those routed, no bounded expert pass on
   the whole buffer; the train step's attention traced to the
   grouped-head causal kernel (``{"pallas": n}``) and to nothing else; the
   rules traced by ``gdn.geometry_counts()`` at the configuration's chunk,
   heads and widths, one a run of Gated DeltaNet blocks a program (the cut
   ``L L L F`` is one run) on every packed grid; the engine's logprobs of
   the first ``REFERENCE_TOKENS`` tokens of the LONGEST trajectory of any
   batch THAT THE PACKER PLACED BEHIND ANOTHER in its row (so every
   rule's state and convolution reset in front of it, and attention masks
   it from the documents ahead) against the configuration's reference run
   on that trajectory alone, within the tolerances below — over all of
   them, and over the ``HEAD_TOKENS`` just behind the boundary; and, on
   the same tokens, the first block's gated delta rule ALONE, the
   program's chunked form against the reference's recurrence
   (:func:`rule_error`: the state's precision, which the logprobs cannot
   see), and the first block's mixer — behind the documents ahead of the
   trajectory in its row — and expert layer alone in the compute dtype
   (:func:`block_errors`: a branch enters the stream at a few per cent of
   a unit-scale embedding, and 3 % of the routed pairs land here). None of
   it depends on how many steps the window holds;
 - ``n_params`` is the cut's (``gdn_cost.share_params``);
 - the rules and attention calls the traced steps ran, the program's
   trace-time counts of them, ``blocks``, the share's routing counters and
   the program's gauges ``train/docs_per_row`` and
   ``train/gdn_resets_in_chunk_per_row`` go into the records and notes for
   the per-layer metrics ``gdn_*`` and ``qnext_*``.
"""

from __future__ import annotations

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
from benchmark import gdn_cost, harness, readers, traffic  # noqa: E402
from benchmark.drivers.train import PackCounter, to_sample  # noqa: E402
from benchmark.drivers.train_ep import build_experiment  # noqa: E402
from benchmark.drivers.train_granite import Gauge  # noqa: E402
from benchmark.drivers.train_hybrid import Placements  # noqa: E402
from benchmark.drivers.train_share import (  # noqa: E402
    InferGrids, build_model, reference_logprobs)

REFERENCE_TOKENS = 8192  # of a trajectory placed second or later in a row

# Engine logprobs (bf16 compute; the chunked rule with float32 decays,
# inverse and state; the grouped-head causal kernel at heads of 256;
# sorted grouped GEMMs over the held experts) against
# reference_qwen3_next (float32 at "highest", the recurrence a token at a
# time, a masked softmax, every held expert on every token), over the
# longest trajectory that sits BEHIND another in its packed row (3519
# tokens behind a document of 5176 in this mix). SET FROM the chip (my
# chip runs, PR 52; PERF.md section 2 has every seed's reading), four
# seeds first, one of them over 2**31 (benchmark/
# check_limits_qwen3_next.py, seeds 11, 2147483659, 1234567, 987654321):
# 0.00657-0.00681 nat on average; 0.037-0.055 at the worst token; the 16
# just behind the boundary 0.0045-0.0079 on average. The mean limit is
# 1.6 x the largest measured, the max limit 2.2 x, the head limit 3.2 x
# (16 tokens). What fails them, the same engine against a WRONG reference
# (same seeds; mean / max / head): RoPE on all 256 dims 0.0165-0.0172 /
# 0.159-0.181 / 0.038-0.048 (one attention block of four: 1.5 x over the
# mean limit, 1.3 x over the max, 1.5 x over the head); THE RESET LEFT OFF
# 0.0078-0.0083 / 0.71-1.71 / 0.149-0.289 — under the mean limit (a drawn
# A_log forgets within a few tokens), refused by the max limit 6 x over
# and the head limit 6 x over; the gates not renormalised 0.0158-0.0167 /
# 0.117-0.168 / 0.013-0.016 (3 % of the pairs land here: over the mean
# limit 1.4 x, and see ROUTED below); every matrix product in
# float8_e4m3, the nearest precision below the configuration's bfloat16,
# 0.0646-0.0659 / 0.29-0.37 / 0.052-0.085 — over each limit 2 x and more;
# no shared-expert gate 0.160-0.163 / 0.78-1.06 / 0.13-0.25; beta left at
# 1 0.197-0.207 / 0.87-1.16 / 0.14-0.26; no l2 norm of q and k 0.331-0.344
# / 1.5-1.8 / 0.28-0.35; the norm weight without its 1 + 0.79-0.80 / 3.7-3.8
# / 0.75-1.0. The rule's state in bfloat16 moves NO logprob (to the
# digit): RULE below is what refuses it.
LOGPROB_MAX_ERR = 0.12
LOGPROB_MEAN_ERR = 0.011
HEAD_TOKENS = 16  # the logprobs just behind the row's boundary
LOGPROB_HEAD_ERR = 0.025
# rule_error, the first block's gated delta rule alone in float32: the
# program's chunked form against the reference's recurrence, as the
# median over tokens of |difference| / |reference|. Same four seeds:
# 1.6e-7 to 1.9e-7 (the limit is 50 x that); the reference's state rounded
# to bfloat16 after every token reads 1e-3 (PERF.md section 2).
RULE_MEDIAN_REL_ERR = 1e-5
# block_errors, the first block's halves alone in the compute dtype (see
# there): medians over tokens of |difference| / |reference|. Same four
# seeds, as published / the controls that move it: the mixer 0.00701-
# 0.00703 (limit 2.8 x) / float8 0.096, beta at 1 0.30, no l2 norm 0.46;
# its 16 tokens behind the boundary 0.0071-0.0075 (limit 4 x) / the reset
# left off 0.18-0.24; the whole expert layer 0.00436-0.00439 (limit 3.4 x)
# / float8 0.0745-0.0749, no shared-expert gate 0.49-0.50; its routed part
# over the 868-1044 of 3519 tokens that chose a held expert
# 0.00576-0.00593 (limit 3.4 x) / float8 0.0745-0.0752, gates not
# renormalised 7.4.
MIXER_MEDIAN_REL_ERR = 0.02
MIXER_HEAD_REL_ERR = 0.03
MOE_MEDIAN_REL_ERR = 0.015
ROUTED_MEDIAN_REL_ERR = 0.02
# (token, expert) pairs on this chip over pairs routed: held / 512 under
# an even router; the band is 0.7 x to 1.35 x the even share, as the
# Nemotron and Trinity cells'.
LOCAL_SHARE_BAND = (0.7, 1.35)
GAUGES = ("train/docs_per_row", "train/gdn_resets_in_chunk_per_row")


def compare_logprobs(got: np.ndarray, ref: np.ndarray) -> Dict[str, Any]:
    cmp = dl.compare_logprobs(got, ref)
    err = np.abs(np.asarray(got, np.float64) - np.asarray(ref, np.float64))
    cmp["head_mean_err"] = float(err[:HEAD_TOKENS].mean())
    cmp["ok"] = bool(np.isfinite(np.asarray(got, np.float64)).all()
                     and cmp["max_err"] <= LOGPROB_MAX_ERR
                     and cmp["mean_err"] <= LOGPROB_MEAN_ERR
                     and cmp["head_mean_err"] <= LOGPROB_HEAD_ERR)
    return cmp


def local_share(cfg_file: Dict[str, Any]) -> Tuple[float, float]:
    even = cfg_file["num_experts"] / cfg_file["num_routed_experts"]
    return LOCAL_SHARE_BAND[0] * even, LOCAL_SHARE_BAND[1] * even


def placed_later(ifaces, model, inf_spec, samples, placements: Placements,
                 ) -> Optional[Tuple[np.ndarray, np.ndarray, Dict[str, Any]]]:
    """(engine logprobs, tokens, where) of the first ``REFERENCE_TOKENS``
    tokens of the longest trajectory of any of ``samples`` that the packer
    placed behind another in its row, out of ONE inference pass a batch — a
    causal prefix of a document stands alone. None where every trajectory
    starts its row."""
    best = None
    for b, sample in enumerate(samples):
        prox = ifaces["actor_inf"].inference(
            model, sample, inf_spec).data["prox_logprobs"]
        lens = [int(n) for n in sample.total_lens("packed_input_ids")]
        later = [i for i, (_, _, col) in placements.at.items() if col > 0]
        if not later:
            continue
        i = max(later, key=lambda j: lens[j])
        if best is not None and lens[i] <= best[2]["length"]:
            continue
        start = sum(lens[:i])
        n_ref = min(lens[i], REFERENCE_TOKENS)
        mb, row, col = placements.at[i]
        ahead = sorted((c, j) for j, (m, r, c) in placements.at.items()
                       if (m, r) == (mb, row) and c < col)
        where = {"batch": b, "trajectory": i, "length": lens[i],
                 "micro_batch": mb, "row": row, "column": col,
                 "tokens": n_ref, "ahead_in_row": [j for _, j in ahead]}
        best = (np.asarray(prox[start + 1:start + n_ref]), np.asarray(
            sample.data["packed_input_ids"][start:start + n_ref]), where)
    return best


def rule_error(engine, cfg_file: Dict[str, Any], toks) -> Dict[str, Any]:
    """THE RULE ALONE, which the logprobs cannot see (a drawn ``A_log``
    forgets within a few tokens, so a state kept in too few bits moves no
    logprob): the first Gated DeltaNet block's rule on ``toks`` — the
    program's ``gdn.gated_delta_rule`` (chunks, the inverse, the scan over
    the chunks' states) against the reference's ``delta_rule`` (a token at
    a time), both in float32 at "highest" on the SAME q, k, v, g and beta
    (the reference's, from the normed embedding) — as the median over the
    tokens of |difference| / |reference| over a token's heads."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.models import gdn

    reference = importlib.import_module("benchmark." + cfg_file["reference"])
    layer = {k: w[0] for k, w in engine.params["layers"]["gdn"].items()}
    toks = jnp.asarray(toks, jnp.int32)
    r = (cfg_file["linear_num_value_heads"]
         // cfg_file["linear_num_key_heads"])
    with jax.default_matmul_precision("highest"):
        u = reference.rms(reference.f32(engine.params["embedding"][toks]),
                          layer["ln1"], reference.eps_of(cfg_file))
        q, k, v, g, beta, _ = reference.gdn_rule_inputs(u, cfg_file, layer)
        want = np.asarray(reference.delta_rule(q, k, v, g, beta), np.float64)
        got = np.asarray(jax.jit(gdn.gated_delta_rule, static_argnums=6)(
            q[None, :, ::r], k[None, :, ::r], v[None], g[None], beta[None],
            jnp.ones((1, len(toks)), jnp.int32), engine.cfg.gdn.chunk_size
        )[0], np.float64)
    flat = (len(toks), -1)
    size = np.linalg.norm(want.reshape(flat), axis=-1)
    rel = np.linalg.norm((got - want).reshape(flat), axis=-1)[
        size > 0] / size[size > 0]
    return {"tokens": int(rel.size), "median_rel_err": float(np.median(rel)),
            "max_rel_err": float(rel.max()),
            "ok": bool(rel.size > 0
                       and np.median(rel) <= RULE_MEDIAN_REL_ERR)}


def row_of(sample, where: Dict[str, Any]) -> Tuple[np.ndarray, np.ndarray]:
    """(tokens, segment ids) of the packed row in front of and with the
    trajectory ``where`` names: the documents ahead of it in its row, then
    its first ``where["tokens"]`` tokens."""
    lens = [int(n) for n in sample.total_lens("packed_input_ids")]
    ids = np.asarray(sample.data["packed_input_ids"])
    docs = [ids[sum(lens[:j]):sum(lens[:j + 1])]
            for j in where["ahead_in_row"]]
    start = sum(lens[:where["trajectory"]])
    docs.append(ids[start:start + where["tokens"]])
    seg = np.concatenate([np.full(len(d), i + 1, np.int32)
                          for i, d in enumerate(docs)])
    return np.concatenate(docs), seg


def _rel_err(got, want) -> np.ndarray:
    """|got - want| / |want| a token, over the tokens where want != 0."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    size = np.linalg.norm(want, axis=-1)
    return np.linalg.norm(got - want, axis=-1)[size > 0] / size[size > 0]


def block_errors(engine, cfg_file: Dict[str, Any], row, seg,
                 ) -> Dict[str, Any]:
    """THE FIRST BLOCK'S TWO HALVES ALONE, in the dtype the timed path
    computes in, where the logprobs are blind (the embedding is drawn at
    unit scale, so a branch enters the stream at a few per cent of it; 3 %
    of the routed pairs land on this chip): the program's ``gdn.gdn_mixer``
    on the packed row ``row`` / ``seg`` (the documents ahead, then the
    trajectory: the state and the convolution reset in front of it) and
    its ``moe.moe_mlp`` — on the engine's compute-dtype copy of that
    layer's weights — against the reference's ``gdn`` and ``moe`` on the
    trajectory ALONE, both on the same normed embedding rounded to the
    compute dtype. As the median over the trajectory's tokens of
    |difference| / |reference|: of the mixer (and the mean of that over
    the ``HEAD_TOKENS`` just behind the boundary, where a missing reset
    shows), of the whole expert layer, and of its routed part alone over
    the tokens that chose a held expert."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.models import gdn, moe

    reference = importlib.import_module("benchmark." + cfg_file["reference"])
    layer = {k: w[0] for k, w in engine.params["layers"]["gdn"].items()}
    copy = {k: w[0] for k, w
            in engine.compute_params()["layers"]["gdn"].items()}
    row, seg = jnp.asarray(row, jnp.int32), jnp.asarray(seg, jnp.int32)
    start = int(np.argmax(np.asarray(seg) == int(seg[-1])))
    eps = reference.eps_of(cfg_file)
    with jax.default_matmul_precision("highest"):
        u = reference.rms(reference.f32(engine.params["embedding"][row]),
                          layer["ln1"], eps).astype(copy["gdn_qkvz"].dtype)
        alone = reference.f32(u[start:])
        want_mix = reference.gdn(alone, cfg_file, layer)
        want_moe = reference.moe(alone, cfg_file, layer)
        want_routed = reference.routed(alone, cfg_file, layer)
    got_mix = jax.jit(lambda u, lp, seg: gdn.gdn_mixer(
        u, lp, engine.cfg.gdn, eps, seg))(u[None], copy, seg[None])[0, start:]
    run = jax.jit(lambda u, lp: moe.moe_mlp(u, lp, engine.cfg.moe)[0])
    got_moe = run(u[None, start:], copy)[0]
    got_routed = run(u[None, start:], {
        k: w for k, w in copy.items() if not k.startswith("s_")})[0]
    mix, routed = _rel_err(got_mix, want_mix), _rel_err(got_routed,
                                                        want_routed)
    out = {"tokens": int(mix.size), "behind": start,
           "mixer_median_rel_err": float(np.median(mix)),
           "mixer_head_rel_err": float(mix[:HEAD_TOKENS].mean()),
           "moe_median_rel_err": float(np.median(_rel_err(got_moe,
                                                          want_moe))),
           "routed_tokens": int(routed.size),
           "routed_median_rel_err": float(np.median(routed))
           if routed.size else None}
    out["ok"] = bool(
        out["mixer_median_rel_err"] <= MIXER_MEDIAN_REL_ERR
        and out["mixer_head_rel_err"] <= MIXER_HEAD_REL_ERR
        and out["moe_median_rel_err"] <= MOE_MEDIAN_REL_ERR
        and routed.size > 0
        and out["routed_median_rel_err"] <= ROUTED_MEDIAN_REL_ERR)
    return out


def kernel_calls(cfg: Dict[str, Any], infer_grids: Dict[str, int],
                 train_grids: Dict[str, int],
                 remat_plan: Dict[str, Dict[str, Any]],
                 ) -> Tuple[List[Dict[str, Any]], List[Dict[str, Any]]]:
    """(the rules, the attention calls) some steps ran, for the rooflines:
    each micro-batch of a grid ``RxL`` runs one rule a Gated DeltaNet block
    and one causal attention call an attention block a pass — forward in
    the inference pass; in the train pass forward, the forward its
    backward re-runs (the rule always: no checkpoint policy keeps its
    products, they carry batch dimensions; the kernel only where the
    grid's grad program keeps nothing of it) and backward."""
    layers = gdn_cost.layer_counts(cfg)
    rules, attns = [], []
    for grids, train in ((infer_grids, False), (train_grids, True)):
        for key, n_mbs in grids.items():
            R, L = (int(x) for x in key.split("x"))
            entry = remat_plan.get(key, {}).get("entry")
            rules.append({
                "rows": R, "length": L,
                "k_heads": cfg["linear_num_key_heads"],
                "v_heads": cfg["linear_num_value_heads"],
                "dk": cfg["linear_key_head_dim"],
                "dv": cfg["linear_value_head_dim"],
                "fwd": n_mbs * layers["gdn"] * (2 if train and entry else 1),
                "bwd": n_mbs * layers["gdn"] if train else 0})
            attns.append({
                "rows": R, "length": L,
                "fwd": n_mbs * layers["full"] * (
                    2 if train and entry == "full" else 1),
                "bwd": n_mbs * layers["full"] if train else 0})
    return rules, attns


def main() -> int:
    spec = dl.load_spec()
    t, out = spec["traffic"], spec["out"]
    split: Dict[str, float] = {"imports_s": time.time() - spec["t0"]}
    t_mark = time.time()
    import jax

    from areal_tpu.base.compile_watch import enable_compilation_cache
    from areal_tpu.models import gdn
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
    gauges = {name: Gauge(name) for name in GAUGES}
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
    for g in gauges.values():
        g.values.clear()

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
    layers = gdn_cost.layer_counts(cfg_file)
    # attention through the grouped-head causal kernel and nothing else
    # (no attention block in a cut shorter than the period: none traced)
    want = (set() if not layers["full"] else
            {"pallas"} if spec["platform"] == "tpu" else {"reference"})
    # every rule at the configuration's chunk, heads and widths, one a run
    # of Gated DeltaNet blocks a program (the cut L L L F is one run)
    runs = gdn_cost.gdn_runs(cfg_file)
    rules = gdn.geometry_counts()
    gdn_geometry = {"%dx%d/%d/k%dv%d/%dx%d" % g: c for g, c in rules.items()}
    geometry = (engine.cfg.gdn.chunk_size, cfg_file["linear_num_key_heads"],
                cfg_file["linear_num_value_heads"],
                cfg_file["linear_key_head_dim"],
                cfg_file["linear_value_head_dim"])
    kernel_ok = set(attn.get("train", {})) == want and bool(rules) and all(
        g[2:] == geometry and c % runs == 0 for g, c in rules.items())
    # the share of the expert layer: nothing dropped in any step, no pass
    # on the whole buffer, and the even share of the pairs landed here
    every = warm_stats + stats
    dropped = [st.get("moe_dropped_frac") for st in every]
    dropless = all(d == 0.0 for d in dropped)
    local = [st.get("moe_local_rows", float("nan")) / st["moe_routed_rows"]
             for st in every]
    lo, hi = local_share(cfg_file)
    share_ok = all(lo <= x <= hi for x in local)
    full_passes = sum(st.get("moe_full_passes", 0.0) for st in every)
    # a trajectory behind another in its row, against the reference alone
    found = placed_later(ifaces, model, inf_spec, samples, placements)
    if found is None:
        cmp, where = {"ok": False, "why": "no trajectory placed later"}, None
    else:
        got, toks, where = found
        cmp = compare_logprobs(
            got, reference_logprobs(engine.params, cfg_file, toks))
        cmp["rule"] = rule_error(engine, cfg_file, toks)
        cmp["block"] = block_errors(
            engine, cfg_file, *row_of(samples[where["batch"]], where))
        cmp["ok"] = cmp["ok"] and cmp["rule"]["ok"] and cmp["block"]["ok"]
    correct = (bad_steps == 0 and abs(first_imp - 1.0) < 0.05 and kernel_ok
               and dropless and share_ok and full_passes == 0 and cmp["ok"]
               and window_compiles == 0 and thr["tok_s"] is not None)

    remat_plan = engine.remat_plan()
    gauge = {name: statistics.fmean(g.values) if g.values else None
             for name, g in gauges.items()}

    def summed(key: str, only_traced: bool) -> Dict[str, int]:
        tot: Dict[str, int] = {}
        for x in steps:
            if x["traced"] or not only_traced:
                for g, c in x[key].items():
                    tot[g] = tot.get(g, 0) + c
        return tot

    rule_calls, attn_calls = kernel_calls(
        cfg_file, summed("infer_mbs", True), summed("train_mbs", True),
        remat_plan)
    traced_steps = [(st, x) for st, x in zip(stats, steps) if x["traced"]]
    load_ratio = [st["moe_expert_load_ratio"] for st in stats]
    notes.append(f"steps={len(steps)} window={elapsed:.3f}s "
                 f"tok_s={thr['tok_s']} mean_tok_s={thr['mean_tok_s']} "
                 f"slow_step_s={thr['slow_step_s']} "
                 f"step_secs={[round(x['secs'], 3) for x in steps]} "
                 f"batch_tokens={batch_tokens} "
                 f"first_importance_weight={first_imp:.4f} attention={attn} "
                 f"blocks={engine.cfg.block_counts()} gauges={gauge} "
                 f"moe_dropped_frac_max={max(dropped)} "
                 f"moe_local_share={min(local):.4f}..{max(local):.4f} "
                 f"moe_full_passes={full_passes} "
                 f"moe_expert_load_ratio={statistics.fmean(load_ratio):.4f} "
                 f"reference={cmp} reference_of={where} "
                 f"window_compiles={window_compiles} "
                 f"window_cache_hits={window_cache_hits} "
                 f"grids={grids} infer_grids={summed('infer_mbs', False)} "
                 f"remat_plan={remat_plan} gdn_geometry={gdn_geometry} "
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
            "n_params": gdn_cost.share_params(cfg_file),
            "state_bytes": state_bytes,
            "blocks": engine.cfg.block_counts(),
            # the rules as the program traced them, and the rules and the
            # attention calls the traced steps ran
            "gdn_geometry": gdn_geometry,
            "gdn_rule_calls_traced": rule_calls,
            "qnext_attn_calls_traced": attn_calls,
            # the program's gauges, over the window's train batches
            "docs_per_row": gauge[GAUGES[0]],
            "gdn_resets_in_chunk_per_row": gauge[GAUGES[1]],
            "moe_expert_load_ratio": statistics.fmean(load_ratio),
            "moe_dropped_frac_max": max(dropped),
            "moe_full_passes": full_passes,
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
