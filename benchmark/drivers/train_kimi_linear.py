"""Driver ``train_kimi_linear``: ``train_share``'s step —
``PPOActorInterface`` ``inference`` then ``train_step`` on packed
trajectory batches, on ONE chip's share of a model whose expert layers are
shared by an expert-parallel group — for a Kimi-Linear model
(``model_type`` kimi_linear): Kimi Delta Attention (the delta rule with a
decay a key CHANNEL, 32 heads of 128) on three blocks of four, latent
attention without a query latent, with nothing rotated and a value head
(128) narrower than its key (192) on the fourth, a dense leading block
before blocks of 256 sigmoid-routed experts (8 a token, a choice bias,
gates x 2.446) beside a shared expert, an untied head, in micro-batches of
up to 16,384 tokens (one row of 10,752 or two of 7,552) whose rows hold two
or three chains of thought. The
configuration holds ``num_experts`` of the ``num_routed_experts`` the
router scores, published block 1 and the whole period 5-8, and a slice of
the vocabulary, and the program runs them with no other chip and nothing
standing in for one.

It is the files before it where it can be (the run itself — the model and
its weights by the program's own init from ``--seed``, the hooks around
the packer, the warm-up, the window, the share's routing checks, the
counters and the result — from ``benchmark/sharelib.py``; the model from
``drivers/train.py``; the reference call from ``drivers/train_share.py``;
the placed trajectories from ``drivers/train_glm4_moe_lite.py``; a
trajectory's row from ``drivers/train_qwen3_next.py``) and differs in its
limits and checks, which are constants of this file:

 - ``correct`` wants: platform, finite loss and grad-norm every step, the
   first importance weight within 0.05 of 1, every generated token trained
   in the recipe's optimizer steps, 0 compiles in the window, no (token,
   expert) pair dropped in any step, the pairs that landed on this chip
   within ``LOCAL_SHARE_BAND`` of those routed, no bounded expert pass on
   the whole buffer; every rule traced as the kernel pair
   (``kda.rule_impl_counts()`` holds ``pallas`` alone) at the
   configuration's heads, one a run of KDA blocks a program, on every
   packed grid; the train step's attention traced to the grouped-head
   causal kernel (``{"pallas": n}``) and to nothing else, the assemblies
   traced by ``mla.geometry_counts()`` without a query latent at the
   configuration's sizes; the engine's logprobs of ALL tokens of the
   batches' LONGEST trajectory and of the longest one THAT THE PACKER
   PLACED BEHIND ANOTHER in its row (the state and the convolutions reset
   in front of it, attention is masked from the documents ahead), taken
   from the timed path at the timed sizes AFTER the window, against the
   configuration's reference run on each trajectory alone, within the
   tolerances below — over all of them, and over the ``HEAD_TOKENS`` just
   behind the row's boundary; and, on the second one's tokens, the first
   KDA block's mixer ALONE, the attention block's branch ALONE (the
   program's ``_block`` with the FFN's last matrices zeroed, on the packed
   row, in the compute dtype) and the first expert layer alone in the
   compute dtype and in float32 on the masters (:func:`block_errors`: at
   drawn weights the whole model's logprobs are blind to a decay averaged
   over a head's channels, a rotated key or a wrong eighth expert), and
   the first KDA block's RULE alone in float32 against the recurrence a
   token at a time (:func:`rule_error`: a carried state kept in too few
   bits moves nothing else). None of it depends on how many steps the
   window holds;
 - ``n_params`` is the cut's (``kda_cost.share_params``);
 - the rules and attention calls the traced steps ran — attention by the
   packer's DOCUMENTS (``sharelib.Layouts``) —, the program's trace-time
   counts of them, ``blocks``, the share's routing counters and the
   program's gauge ``train/kda_resets_in_chunk_per_row`` go into the
   records and notes for the per-layer metrics ``kda_*`` and ``kimi_*``.
"""

from __future__ import annotations

import importlib
import os
import sys
from typing import Any, Dict, List, Tuple

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import driverlib as dl  # noqa: E402
from benchmark import kda_cost, sharelib  # noqa: E402
from benchmark.drivers.train import build_model  # noqa: E402
from benchmark.drivers.train_glm4_moe_lite import placed  # noqa: E402
from benchmark.drivers.train_qwen3_next import _rel_err, row_of  # noqa: E402
from benchmark.drivers.train_share import reference_logprobs  # noqa: E402

# Engine logprobs (bf16 compute; gates, decays and the carried state in
# float32; the kernel pair kda_rule_fwd / _bwd; the grouped-head causal
# kernel at a key of 192 in 256 lanes over a value of 128; sorted grouped
# GEMMs over the 8 held experts) against reference_kimi_linear (float32 at
# "highest", the rule a token at a time, a masked softmax of one document,
# every held expert on every token), over ALL tokens of the longest
# trajectory (``first``: 6,480 tokens, at the head of its row) and of the
# longest one BEHIND another in its packed row (``later``: 5,062 tokens
# behind 1,682). SET FROM the chip (my chip runs, PR 63; PERF.md section 2
# has every seed's reading): benchmark/check_limits_kimi_linear.py on seeds
# 11, 2147483659 (over 2**31) and 1234567, and four runs of the cell (seeds
# 2163000102-105, after a window's optimizer steps). As published, mean /
# max / the 8 behind the boundary, 14 readings: 0.0193-0.0207 / 0.195-0.266
# / 0.0080-0.0294. What fails the limits, the same engine against a WRONG
# reference on ``later`` (three seeds; mean / max / head, then the blocks'
# numbers where they move): every matrix product in float8_e4m3, the
# nearest precision below the configuration's bfloat16, 0.163-0.168 /
# 0.75-0.83 / 0.09-0.21 — over the mean limit 6 x, UNDER the max limit,
# KDA mixer 0.081, attention branch 0.087-0.088, expert layer 0.0745; THE
# DECAY AVERAGED OVER A HEAD'S CHANNELS (a Gated DeltaNet rule in KDA's
# place) 0.81-0.83 / 3.8-5.1, mixer 0.88-0.90; delta before decay
# 0.039-0.042, mixer 0.0191-0.0205, the rule alone 0.016-0.018; silu for
# the output gate's sigmoid 1.05-1.08, mixer 4.5; no l2 norm: the
# reference's state overflows (not finite: refused), mixer 1.29; beta
# left out 0.58-0.59; exp(A_log) left out 0.73-0.76; no dt_bias 1.03;
# taps reversed 1.01; RoPE ON THE LATENT ATTENTION 0.0209-0.0215 — under
# the mean limit: one block of five, behind a scale that makes its softmax
# nearly flat — and its branch 0.587-0.609 against 0.0061; kv_a_layernorm
# over all 576 0.0195-0.0202, branch 0.0343-0.0353; the scale 128^-0.5
# 0.0197-0.0204, branch 0.169-0.173; kv_b_proj read [v | k_nope]
# 0.123-0.139, branch 1.41; THE RESET AT A DOCUMENT START LEFT OFF (state,
# taps and attention run over the row as one document) 0.0448-0.0456 /
# 2.4-2.8 / 0.59-1.07, the mixer's 8 tokens behind the boundary 0.90-0.92,
# the branch's 16.2-16.7; the bias left out of the choice 0.0216-0.0238 —
# over the largest reading by 4 % only — and the expert layer in float32,
# mean over the routed tokens, 0.016-0.021 against 2.1e-7; gates not
# renormalised 0.30-0.33, expert layer 0.78; THE 2.446 LEFT OUT
# 0.038-0.043, expert layer 0.181; the 2.446 on the shared expert too
# 0.43-0.44; no shared expert 0.38-0.39; softmax for sigmoid 0.041-0.048,
# expert layer 0.108-0.160. ONE control moves no logprob and no branch in
# the compute dtype: THE CARRIED STATE ROUNDED TO BFLOAT16 EVERY CHUNK —
# ``rule_error`` below is there for it. The mean limit lies between the
# largest of the 14 readings (0.0207) and the lowest of the control nearest
# above it that no block's own limit refuses (the 2.446 left out, 0.0384;
# delta before decay, 0.0389): 25 % over the one, 32 % under the other.
# The max limit between the largest reading (0.266) and the lowest of the
# control it is there for (the reset left off, 2.44) — a token that is
# WRONG, not one whose eighth expert differs; the head limit 4 x the
# largest reading and a fifth of that control's lowest (0.59). HELD SINCE
# by 30 more readings over fifteen seeds of the cell, at micro-batches of
# 8,192, 12,288 and 16,384 (the compared trajectories and their rows'
# neighbours are the same at each): 0.0186-0.0208 / 0.161-0.327 /
# 0.0111-0.0344 (PERF.md section 2; docs/perf_history.md has each).
LOGPROB_MAX_ERR = 1.2
LOGPROB_MEAN_ERR = 0.026
HEAD_TOKENS = 8  # the logprobs just behind the row's boundary
LOGPROB_HEAD_ERR = 0.12
# block_errors, the blocks' pieces alone (see there): over the later
# trajectory's tokens, |difference| / |reference|. Same seven seeds, as
# published / the lowest control that moves it. In the compute dtype,
# medians: the KDA mixer 0.0089-0.0091 (limit 1.43 x) / delta before decay
# 0.0191; its 8 tokens behind the boundary 0.0076-0.0081 (limit 1.5 x) /
# delta before decay 0.0158, the reset left off 0.90; the attention branch
# 0.0061-0.0062 (limit 1.9 x) / the k/v norm over all 576 0.0343; its 8
# tokens behind the boundary 0.0055-0.0058 (limit 1.7 x) / that control
# 0.0157, attention across the boundary 16.2; the expert layer (the shared
# expert with it) over the ~1,100 tokens that chose a held expert
# 0.0043-0.0044 (limit 1.8 x) / float8 0.0745. In FLOAT32 on the masters,
# the expert layer: median 2.1e-7 (limit 1e-4); mean 2.1e-7 (limit 2e-3) /
# the bias left out of the choice 0.016.
KDA_MEDIAN_REL_ERR = 0.013
KDA_HEAD_REL_ERR = 0.012
ATTN_MEDIAN_REL_ERR = 0.012
ATTN_HEAD_REL_ERR = 0.010
MOE_MEDIAN_REL_ERR = 0.008
MOE_F32_MEDIAN_REL_ERR = 1e-4
MOE_F32_MEAN_REL_ERR = 2e-3
# rule_error, the first KDA block's rule alone in float32: the program's
# chunked form (the kernels where the timed path runs them) against the
# reference's recurrence a token at a time, as the median over tokens of
# |difference| / |reference|: what refuses a carried state kept in too few
# bits, which moves no logprob and no branch in the compute dtype. Seven
# readings 5.9e-5 to 7.9e-5 (the kernels' float32 is the MXU's six
# bfloat16 passes and the chip's exponential, over 5,062 tokens of a state
# that lasts hundreds; the XLA form on the CPU reads 3.6e-7); the
# reference's state rounded to bfloat16 every 64 tokens: PERF.md section 2.
RULE_MEDIAN_REL_ERR = 2e-4
# The (token, expert) pairs that land on the 8 held experts, over the even
# router's 8 / 256 of those routed. ISSUE 63 named the other share cells'
# band, 0.7-1.35; SET FROM the chip instead (my chip run, PR 63: weights
# and ids of 42 seeds drawn as the cell draws them, one forward of 16,384
# tokens each; six of them are seeds of the cell's own runs, which read
# within 3 % of it): a drawn 256-wide sigmoid router is far from even (the
# busiest expert is chosen 4.3-6.5 x the mean: the mixers' normed outputs
# dominate the stream and change slowly along a document), so the share of
# 8 experts x 4 layers reads 0.980 +- 0.147 x the even one by seed, 0.652
# to 1.264 — the issue's band is -1.9 / +2.5 standard deviations and
# refused seed 2163329651 (0.652) with everything else right. The band is
# -3.3 / +4.2: what it is there for — a layer told a wrong share (16 or 64
# holders: 2.0 x / 0.5 x), every expert local (32 x), none (0) — stays out.
LOCAL_SHARE_BAND = (0.5, 1.6)
GAUGES = ("train/kda_resets_in_chunk_per_row",)


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
    even = cfg_file["num_experts"] / (
        cfg_file.get("num_routed_experts") or cfg_file["num_experts"])
    return LOCAL_SHARE_BAND[0] * even, LOCAL_SHARE_BAND[1] * even


def first_kind(engine, kda: bool, dense: bool = False) -> str:
    """The kind of the model's first block that mixes with KDA (``kda``)
    or attention, whose FFN is the dense MLP (``dense``) or the experts;
    None where it has none (a rehearsal's cut to two blocks)."""
    from areal_tpu.models.config import KDA, attention_kind, has_dense_ffn

    return next((k for k in engine.cfg.layer_kinds
                 if (attention_kind(k) == KDA) == kda
                 and has_dense_ffn(k) == dense), None)


def rule_error(engine, cfg_file: Dict[str, Any], toks) -> Dict[str, Any]:
    """THE RULE ALONE, in float32, which neither the logprobs nor a branch
    in the compute dtype can see to the last bits: the first KDA block's
    rule on ``toks`` — the program's ``kda.channel_decay_rule`` as the
    timed path dispatches it (chunks, sub-blocks, the inverse, the states'
    chain) against the reference's ``delta_rule`` (a token at a time) on
    the SAME q, k, v, g and beta (the reference's, from the normed
    embedding) — as the median over the tokens of |difference| /
    |reference| over a token's heads."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.models import kda

    reference = importlib.import_module("benchmark." + cfg_file["reference"])
    kind = first_kind(engine, kda=True, dense=True)
    m = {k: w[0] for k, w in engine.params["layers"][kind].items()}
    toks = jnp.asarray(toks, jnp.int32)
    with jax.default_matmul_precision("highest"):
        u = reference.rms(reference.f32(engine.params["embedding"][toks]),
                          m["ln1"], reference.eps_of(cfg_file))
        q, k, v, g, beta, _ = reference.kda_inputs(u, cfg_file, m)
        want = reference.delta_rule(q, k, v, g, beta)
        how = kda._rule_impl(engine.attn_impl, engine.cfg.kda, jnp.float32)
        got = jax.jit(lambda *a: kda.channel_decay_rule(
            *a, jnp.ones((1, len(toks)), jnp.int32),
            engine.cfg.kda.chunk_size, how))(
                *(a[None] for a in (q, k, v, g, beta)))[0]
    T = len(toks)
    err = _rel_err(np.asarray(got).reshape(T, -1),
                   np.asarray(want).reshape(T, -1))
    out = {"tokens": T, "impl": how, "median_rel_err": float(np.median(err)),
           "max_rel_err": float(err.max())}
    out["ok"] = bool(out["median_rel_err"] <= RULE_MEDIAN_REL_ERR)
    return out


def mixer_branch(engine, kind: str, copy, u, seg):
    """What the block of ``kind`` adds to the stream ``u`` [T, D] of a
    packed row ``seg`` through its MIXER BRANCH alone: the program's
    ``transformer._block`` (norm, the mixer as the timed path runs it —
    the row padded to whole lanes —, the out-projection) on the layer
    ``copy`` with the FFN's last matrices zeroed, less the stream."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.models import transformer

    cfg = engine.cfg
    pad = -len(seg) % 128
    seg_p = jnp.pad(seg, (0, pad))[None]
    first = jnp.concatenate([jnp.ones(1, bool), seg[1:] != seg[:-1]])
    idx = jnp.arange(len(seg))
    pos_p = jnp.pad(idx - jax.lax.cummax(jnp.where(first, idx, 0)),
                    (0, pad))[None]
    h = jnp.pad(u, ((0, pad), (0, 0)))[None]
    lp = {k: jnp.zeros_like(w) if k.endswith("down") else w
          for k, w in copy.items()}

    def run(h, lp, seg, pos):
        return transformer._block(
            cfg, h, lp, None, None, seg, pos, None, None, None,
            engine.attn_impl, kind=kind)[0] - h

    return jax.jit(run)(h, lp, seg_p, pos_p)[0, :len(seg)]


def block_errors(engine, cfg_file: Dict[str, Any], row, seg,
                 across: bool = False) -> Dict[str, Any]:
    """THE FIRST KDA MIXER, THE ATTENTION BRANCH AND THE FIRST EXPERT
    LAYER ALONE, in the dtype the timed path computes in, where the
    logprobs see little: the program's mixer branches
    (:func:`mixer_branch`) on the packed row ``row`` / ``seg`` (the
    documents ahead, then the trajectory: the state, the convolutions'
    taps and attention stop in front of it) and its ``moe.moe_mlp`` — on
    the engine's compute-dtype copy of those layers' weights — against the
    reference's ``kda``, ``attention`` and ``moe`` on the trajectory
    ALONE, on the same embedding (the branch's own norm in front) or
    normed embedding rounded to the compute dtype. As the median over the
    trajectory's tokens of |difference| / |reference|: of each branch (and
    the mean of that over the ``HEAD_TOKENS`` just behind the boundary,
    where a reset, a tap or a mask that crosses it shows), and of the
    expert layer (the shared expert with it) over the tokens that chose a
    held expert, with it once more in FLOAT32 on the masters. ``across``
    (``check_limits_kimi_linear.py``'s control): the reference's branches
    run over the ROW as one document — a model whose state, taps and
    attention do not stop at a document's start."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.models import moe

    reference = importlib.import_module("benchmark." + cfg_file["reference"])
    kda_kind = first_kind(engine, kda=True, dense=True)
    attn_kind = first_kind(engine, kda=False)
    moe_kind = first_kind(engine, kda=True)
    copy = engine.compute_params()["layers"]
    masters = engine.params["layers"]
    row, seg = jnp.asarray(row, jnp.int32), jnp.asarray(seg, jnp.int32)
    start = int(np.argmax(np.asarray(seg) == int(seg[-1])))
    dtype = copy[kda_kind]["kda_out"].dtype
    eps = reference.eps_of(cfg_file)
    h = engine.params["embedding"][row].astype(dtype)
    out: Dict[str, Any] = {"tokens": int(len(seg) - start), "behind": start}
    branches = (("kda", kda_kind, "kda"), ("attn", attn_kind, "attention"))
    # the program's side in its own precision (the kernels take no
    # "highest" from a context around them)
    got = {name: mixer_branch(engine, kind, {k: w[0] for k, w in
                                             copy[kind].items()}, h, seg)
           for name, kind, _ in branches if kind is not None}
    with jax.default_matmul_precision("highest"):
        for name, kind, branch in branches:
            if kind is None:
                out[f"{name}_median_rel_err"] = out[
                    f"{name}_head_rel_err"] = float("nan")
                continue
            m = {k: w[0] for k, w in masters[kind].items()}
            a = 0 if across else start
            want = getattr(reference, branch)(
                reference.rms(reference.f32(h[a:]), m["ln1"], eps),
                cfg_file, m)[start - a:]
            err = _rel_err(got[name][start:], want)
            out[f"{name}_median_rel_err"] = float(np.median(err))
            out[f"{name}_head_rel_err"] = float(err[:HEAD_TOKENS].mean())
        u = reference.rms(reference.f32(h[start:]),
                          masters[moe_kind]["ln2"][0], eps).astype(dtype)
        moe32 = {k: w[0] for k, w in masters[moe_kind].items()}
        want_moe = reference.moe(reference.f32(u), cfg_file, moe32)
        first = reference.first_held(cfg_file)
        held = np.asarray(reference.gates(reference.f32(u), cfg_file, moe32)[
            :, first:first + cfg_file["num_experts"]].sum(-1) > 0)
        exact = _rel_err(jax.jit(lambda u, lp: moe.moe_mlp(
            u, lp, engine.cfg.moe)[0])(reference.f32(u)[None], moe32)[0][held],
            want_moe[held])
    experts = {k: w[0] for k, w in copy[moe_kind].items()}
    got_moe = jax.jit(lambda u, lp: moe.moe_mlp(u, lp, engine.cfg.moe)[0])(
        u[None], experts)[0]
    routed = _rel_err(got_moe[held], want_moe[held])
    out.update(
        routed_tokens=int(routed.size),
        moe_median_rel_err=float(np.median(routed)) if routed.size else None,
        moe_f32_median_rel_err=float(np.median(exact)) if exact.size else None,
        moe_f32_mean_rel_err=float(exact.mean()) if exact.size else None)
    out["ok"] = bool(
        start > 0 and routed.size > 0
        and out["kda_median_rel_err"] <= KDA_MEDIAN_REL_ERR
        and out["kda_head_rel_err"] <= KDA_HEAD_REL_ERR
        and out["attn_median_rel_err"] <= ATTN_MEDIAN_REL_ERR
        and out["attn_head_rel_err"] <= ATTN_HEAD_REL_ERR
        and out["moe_median_rel_err"] <= MOE_MEDIAN_REL_ERR
        and out["moe_f32_median_rel_err"] <= MOE_F32_MEDIAN_REL_ERR
        and out["moe_f32_mean_rel_err"] <= MOE_F32_MEAN_REL_ERR)
    return out


def kernel_calls(cfg: Dict[str, Any], layouts: List[Tuple[str, str, Tuple]],
                 remat_plan: Dict[str, Dict[str, Any]],
                 ) -> Tuple[List[Dict[str, Any]], List[Dict[str, Any]]]:
    """(the rules, the attention calls) some steps ran, for the rooflines,
    from those steps' micro-batches ``layouts``: each micro-batch of a grid
    ``RxL`` runs one rule a KDA block and one causal attention call an
    attention block a pass — forward in the inference pass; in the train
    pass forward, the forward its backward re-runs (the rule wherever the
    grid's grad program re-runs anything: no checkpoint policy keeps a
    kernel's products; the attention kernel only where it keeps nothing of
    it) and backward. Attention by the micro-batch's DOCUMENTS."""
    n = kda_cost.layer_counts(cfg)
    _, heads, dh, _ = kda_cost.rule_geometry(cfg)
    rules: Dict[str, Dict[str, Any]] = {}
    attns: Dict[Tuple, Dict[str, Any]] = {}
    for which, key, docs in layouts:
        train = which == "train"
        R, L = (int(x) for x in key.split("x"))
        entry = remat_plan.get(key, {}).get("entry")
        r = rules.setdefault(key, {"rows": R, "length": L, "heads": heads,
                                   "dk": dh, "dv": dh, "fwd": 0, "bwd": 0})
        r["fwd"] += n["kda"] * (2 if train and entry else 1)
        r["bwd"] += n["kda"] if train else 0
        a = attns.setdefault((key, docs), {
            "grid": key, "documents": list(docs), "fwd": 0, "bwd": 0})
        a["fwd"] += n["attn"] * (2 if train and entry == "full" else 1)
        a["bwd"] += n["attn"] if train else 0
    return list(rules.values()), list(attns.values())


def main() -> int:
    spec = dl.load_spec()
    share = sharelib.set_up(spec, build_model, GAUGES)
    from areal_tpu.models import kda, mla
    from areal_tpu.ops import attention

    sharelib.measure(share)
    engine, cfg_file = share.engine, spec["config"]
    sound = sharelib.steps_sound(share)
    routed = sharelib.routing(share, local_share(cfg_file))
    tpu = spec["platform"] == "tpu"
    # every rule the kernel pair, at the configuration's heads, one a run
    # of KDA blocks a program, on every packed grid
    impl = kda.rule_impl_counts()
    rules = kda.geometry_counts()
    rule_geometry = {"%dx%d/%d/h%d/%d/r%d" % g: c for g, c in rules.items()}
    kernel_frac = kda.rule_kernel_frac()
    rules_ok = (bool(rules) and (set(impl) == {"pallas"} or not tpu)
                and all(g[2:] == kda_cost.rule_geometry(cfg_file)
                        and c % kda_cost.runs(cfg_file, kda=True) == 0
                        for g, c in rules.items())
                and share.every_grid <= {"%dx%d" % g[:2] for g in rules})
    # attention through the grouped-head causal kernel and nothing else,
    # behind assemblies without a query latent at the configuration's sizes
    attn = attention.dispatch_counts()
    want = {"pallas"} if tpu else {"reference"}
    traced = mla.geometry_counts()
    mla_geometry = {"%dx%d/h%d/q%dkv%d/%d+%d/v%d" % g: c
                    for g, c in traced.items()}
    attn_ok = (set(attn.get("train", {})) == want and bool(traced)
               and all(g[2:] == kda_cost.mla_geometry(cfg_file)
                       for g in traced)
               and share.every_grid <= {"%dx%d" % g[:2] for g in traced})
    # the longest trajectory, and one behind another in its row, each
    # against the reference alone — from the timed path, after the window
    found = placed(share.ifaces, share.model, share.inf_spec, share.samples,
                   share.placements)
    cmp: Dict[str, Any] = {}
    for which, hit in found.items():
        if hit is None:
            cmp[which] = {"ok": False, "why": f"no {which} trajectory"}
            continue
        got, toks, where = hit
        cmp[which] = {**compare_logprobs(
            got, reference_logprobs(engine.params, cfg_file, toks)),
            "where": where}
    if found["later"] is not None:
        where = found["later"][2]
        row, seg = row_of(share.samples[where["batch"]], where)
        cmp["block"] = block_errors(engine, cfg_file, row, seg)
        cmp["rule"] = rule_error(engine, cfg_file, found["later"][1])
    cmp["ok"] = all(v.get("ok", False) for v in cmp.values()) and (
        "block" in cmp and "rule" in cmp)
    correct = (sound["ok"] and rules_ok and attn_ok and routed["ok"]
               and cmp["ok"])

    rule_calls, attn_calls = kernel_calls(
        cfg_file, sharelib.traced_layouts(share), engine.remat_plan())
    sharelib.result(
        share, correct, sound, routed, kda_cost.share_params(cfg_file),
        {"kda_geometry": rule_geometry, "kda_rule_impl": impl,
         "kda_kernel_frac": kernel_frac, "mla_geometry": mla_geometry,
         "kda_rule_calls_traced": rule_calls,
         "kimi_attn_calls_traced": attn_calls},
        f"attention={attn} kda_rule_impl={impl} kda_geometry={rule_geometry} "
        f"mla_geometry={mla_geometry} rules_ok={rules_ok} attn_ok={attn_ok} "
        f"reference={cmp}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
