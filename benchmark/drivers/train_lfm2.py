"""Driver ``train_lfm2``: ``train_share``'s step — ``PPOActorInterface``
``inference`` then ``train_step`` on packed trajectory batches, on ONE
chip's share of a model whose expert layers are shared by an
expert-parallel group — for an LFM2-MoE model (``model_type`` lfm2_moe):
whole blocks of a doubly gated short convolution, 3 to 1 against GQA at
heads of 64 with per-head q/k norms, a dense leading block before blocks
of 64 sigmoid-routed experts (4 a token, a choice bias, no shared expert),
a tied head, in micro-batches of up to 16,384 tokens whose rows (6.9k-7.3k
tokens: the packer sweeps row lengths up to 8192 here and takes the
fullest) hold 2 to 9 documents. The configuration holds ``num_experts`` of
the ``num_routed_experts`` the router scores, the leading dense block,
one whole period of the published layers and a slice of the vocabulary,
and the program runs them with no other chip and nothing standing in for
one.

It is the files before it where it can be (the run itself — the model and
its weights by the program's own init from ``--seed``, the hooks around
the packer, the warm-up, the window, the share's routing checks, the
counters and the result — from ``benchmark/sharelib.py``; the model from
``drivers/train.py``; the reference call from ``drivers/train_share.py``;
the trajectory placed behind another, its row and the share's band from
``drivers/train_qwen3_next.py``) and differs in its limits and checks,
which are constants of this file:

 - ``correct`` wants: platform, finite loss and grad-norm every step, the
   first importance weight within 0.05 of 1, 0 compiles in the window, no
   (token, expert) pair dropped in any step, the pairs that landed on this
   chip within ``LOCAL_SHARE_BAND`` of those routed, no bounded expert
   pass on the whole buffer; the train step's attention traced to the
   grouped-head causal kernel (``{"pallas": n}``) and to nothing else; the
   convolutions traced by ``shortconv.geometry_counts()`` at the
   configuration's channels and taps, one a run of short-convolution
   blocks a program (the cut ``c(dense) A c c c`` is two runs) on every
   packed grid; the engine's logprobs of ALL tokens of the LONGEST
   trajectory of any batch THAT THE PACKER PLACED BEHIND ANOTHER in its
   row (so every block's taps are cut in front of it, and attention masks
   it from the documents ahead) against the configuration's reference run
   on that trajectory alone, within the tolerances below — over all of
   them, and over the ``HEAD_TOKENS`` just behind the boundary (a tap that
   crosses a document start touches two tokens a block, eight after four
   blocks); and, on the same tokens, the first short-convolution block's
   gates and taps ALONE in float32 (:func:`conv_error`: the products'
   precision, which nothing in the compute dtype can see), its mixer —
   behind the documents ahead of the trajectory in its row —, the first
   expert layer alone and the whole first expert block (the attention
   block) in the compute dtype (:func:`block_errors`: an eighth of the
   routed pairs land here, a wrong fourth expert moves a logprob little,
   and at drawn weights q and k have nearly unit RMS before their norm)
   with the expert layer once more in FLOAT32 on the masters (the
   routing's arithmetic with no rounding to hide behind). None of it
   depends on how many steps the window holds;
 - ``n_params`` is the cut's (``shortconv_cost.share_params``);
 - the convolutions and attention calls the traced steps ran — attention
   by the packer's DOCUMENTS (``sharelib.Layouts``) —, the program's
   trace-time counts of them, ``blocks``, the share's routing counters and
   the program's gauges ``train/docs_per_row`` and
   ``train/shortconv_resets_per_row`` go into the records and notes for
   the per-layer metrics ``shortconv_*`` and ``lfm2_*``.
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
from benchmark import sharelib, shortconv_cost  # noqa: E402
from benchmark.drivers.train import build_model  # noqa: E402
from benchmark.drivers.train_qwen3_next import (  # noqa: E402
    _rel_err, local_share, placed_later, row_of)
from benchmark.drivers.train_share import reference_logprobs  # noqa: E402

# Engine logprobs (bf16 compute; the two gates and the taps in float32
# between bf16 projections; the grouped-head causal kernel at heads of 64;
# sorted grouped GEMMs over the 8 held experts) against reference_lfm2
# (float32 at "highest", three shifted products of one document, a masked
# softmax, every held expert on every token), over ALL tokens of the
# longest trajectory that sits BEHIND another in its packed row (3172
# tokens in this mix). SET FROM the chip (my chip runs, PR 56; PERF.md
# section 2 has every seed's reading): benchmark/check_limits_lfm2.py on
# seeds 11, 2147483659 (over 2**31), 1234567, 987654321, the bias as the
# program's init draws it (N(0, 0.005)): 0.0143-0.0150 nat on average;
# 0.218-0.389 at the worst token; the 8 just behind the boundary
# 0.0056-0.0149. The first cut of the cell (another shape_seed, a
# trajectory of 3566, the bias drawn at 0.02 or balanced; 28 seeds) read
# 0.0140-0.0153 / 0.184-0.391 / 0.0076-0.0357: the max is ONE token of
# 3000 at which bfloat16 and float32 choose a different fourth expert, a
# heavy tail. What fails them, the same engine against a WRONG reference
# (the four seeds; mean / max / head, then the blocks' numbers where they
# move): every matrix product in float8_e4m3, the nearest precision below
# the configuration's bfloat16, 0.143-0.150 / 0.64-0.70 / 0.07-0.20 —
# over the mean limit 8 x, over the max limit 1.3 x (mixer 0.085, expert
# layer 0.075, block 0.086-0.116); SiLU after the convolution 0.54-0.56 /
# 2.5-2.8 / 0.39-0.75, mixer 1.0; the B gate left out 1.00-1.04 / 4.5-6.1,
# mixer 1.34; the C gate left out 1.01-1.04 / 4.3-5.1, mixer 1.34; the
# taps reversed 0.95-0.98 / 4.2-4.9, mixer and conv 1.13-1.16; THE TAPS
# CROSSING A DOCUMENT START 0.0161-0.0180 / 0.39-1.89 / 0.12-0.59 — at
# the mean limit or under it (two tokens a block), refused by the head
# limit 1.5 x over and more and by the mixer's head 9 x over (0.18);
# softmax for sigmoid 0.038-0.042 / 0.28-0.52, expert layer 0.20-0.23;
# gates not renormalised 0.21 / 1.1-1.4, expert layer 0.70; expert_bias
# LEFT OUT OF THE CHOICE (one token's choice in seven changes)
# 0.0195-0.0224 / 0.25-0.28 — over the mean limit, and the expert layer
# in float32, mean over tokens, 0.036-0.056 against 3.8e-7; THE Q/K NORM
# LEFT OUT (at drawn weights q and k have an RMS of 0.9 before it)
# 0.0190-0.0197 / 0.22-0.30 / 0.031-0.066 — over the mean limit, and the
# attention block 0.286-0.293 against 0.0068-0.0089. TWO controls move no
# logprob limit and are refused by a block's own: expert_bias ADDED TO
# THE GATES 0.0142-0.0149 / 0.22-0.39 — the expert layer in float32,
# median, 0.0031-0.0041 against 3.9e-7; THE CONVOLUTION'S PRODUCTS
# ROUNDED TO BFLOAT16 before they are summed 0.0145-0.0151 / 0.22-0.27 —
# conv_error 1.65e-3 against 4.4e-8. Three more seeds through the cell
# itself (2156000521, 56000522, 56000523) read 0.0143 / 0.0143 / 0.0157,
# 0.200 / 0.217 / 0.326, 0.0086 / 0.0095 / 0.0142. The mean limit lies
# between the largest of the 35 readings as published (0.0157; the
# median is 0.0145, and what lifts a seed is a handful of tokens whose
# fourth expert differs) and the lowest of the control nearest above it
# (0.0195), 15 % over the one and 8 % under the other: narrow, with the
# more room on the side where a fresh seed would refuse a sound program;
# the max limit between 0.391 and the float8 control's 0.62-0.70; the
# head limit 2.2 x the largest of 35 readings and under the crossed
# taps' lowest (0.12).
LOGPROB_MAX_ERR = 0.5
LOGPROB_MEAN_ERR = 0.018
HEAD_TOKENS = 8  # the logprobs just behind the row's boundary
LOGPROB_HEAD_ERR = 0.08
# block_errors, the first blocks' pieces alone (see there): over the
# trajectory's tokens, |difference| / |reference|. Same four seeds, as
# published / the lowest control that moves it. In the compute dtype,
# medians: the mixer 0.00525-0.00526 (limit 2.8 x) / float8 0.085; its 8
# tokens behind the boundary 0.0052-0.0053 (limit 3.8 x) / float8 0.084,
# the taps crossing 0.18; the expert layer over the tokens that chose a
# held expert 0.00510-0.00512 (limit 1.56 x: the reading does not move
# with the seed) / the bias added to the gates 0.0059, float8 0.0745; the
# whole attention block 0.0068-0.0089 (limit 3.4 x) / float8 0.086, no
# q/k norm 0.286. In FLOAT32 on the masters, the expert layer: median
# 3.8e-7 to 3.9e-7 (limit 1e-4) / the bias added to the gates 0.0031;
# mean 3.8e-7 (limit 2e-3: one token of 1500 whose fourth expert ties
# reads 3e-4 to 7e-4) / the bias added to the gates 0.0035, left out of
# the choice 0.036.
MIXER_MEDIAN_REL_ERR = 0.015
MIXER_HEAD_REL_ERR = 0.02
MOE_MEDIAN_REL_ERR = 0.008
MOE_F32_MEDIAN_REL_ERR = 1e-4
MOE_F32_MEAN_REL_ERR = 2e-3
BLOCK_MEDIAN_REL_ERR = 0.03
# conv_error, the first block's gates and taps alone in float32 on the
# same [B | C | x]: 4.4e-8 on all four seeds; each product rounded to
# bfloat16 before the sum reads 1.65e-3.
CONV_MEDIAN_REL_ERR = 1e-5
GAUGES = ("train/docs_per_row", "train/shortconv_resets_per_row")


def compare_logprobs(got: np.ndarray, ref: np.ndarray) -> Dict[str, Any]:
    cmp = dl.compare_logprobs(got, ref)
    err = np.abs(np.asarray(got, np.float64) - np.asarray(ref, np.float64))
    cmp["head_mean_err"] = float(err[:HEAD_TOKENS].mean())
    cmp["ok"] = bool(np.isfinite(np.asarray(got, np.float64)).all()
                     and cmp["max_err"] <= LOGPROB_MAX_ERR
                     and cmp["mean_err"] <= LOGPROB_MEAN_ERR
                     and cmp["head_mean_err"] <= LOGPROB_HEAD_ERR)
    return cmp


def first_of(engine, dense: bool) -> str:
    """The kind of the model's first block whose FFN is the dense MLP
    (``dense``) or the expert layer."""
    from areal_tpu.models.config import has_dense_ffn

    return next(k for k in engine.cfg.layer_kinds
                if has_dense_ffn(k) == dense)


def block_errors(engine, cfg_file: Dict[str, Any], row, seg,
                 ) -> Dict[str, Any]:
    """THE FIRST SHORT-CONVOLUTION MIXER AND THE FIRST EXPERT LAYER ALONE,
    in the dtype the timed path computes in, where the logprobs see
    little (one wrong tap or gate of four conv blocks under a dense FFN
    eight times its published weight; an eighth of the routed pairs land
    on this chip): the program's ``shortconv.shortconv_mixer`` on the
    packed row ``row`` / ``seg`` (the documents ahead, then the
    trajectory: the taps are cut in front of it) and its ``moe.moe_mlp``
    — on the engine's compute-dtype copy of those layers' weights —
    against the reference's ``shortconv`` and ``moe`` on the trajectory
    ALONE, both on the same normed embedding rounded to the compute
    dtype. As the median over the trajectory's tokens of |difference| /
    |reference|: of the mixer (and the mean of that over the
    ``HEAD_TOKENS`` just behind the boundary, where a tap that crosses it
    shows), and of the expert layer over the tokens that chose a held
    expert."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.models import moe, shortconv

    reference = importlib.import_module("benchmark." + cfg_file["reference"])
    conv_kind = first_of(engine, dense=True)
    moe_kind = first_of(engine, dense=False)
    copy = engine.compute_params()["layers"]
    conv = {k: w[0] for k, w in copy[conv_kind].items()}
    experts = {k: w[0] for k, w in copy[moe_kind].items()}
    masters = engine.params["layers"]
    row, seg = jnp.asarray(row, jnp.int32), jnp.asarray(seg, jnp.int32)
    start = int(np.argmax(np.asarray(seg) == int(seg[-1])))
    with jax.default_matmul_precision("highest"):
        u = reference.rms(
            reference.f32(engine.params["embedding"][row]),
            masters[conv_kind]["ln1"][0], reference.eps_of(cfg_file),
        ).astype(conv["sc_in"].dtype)
        alone = reference.f32(u[start:])
        want_mix = reference.shortconv(
            alone, cfg_file, {k: w[0] for k, w in masters[conv_kind].items()})
        moe32 = {k: w[0] for k, w in masters[moe_kind].items()}
        want_moe = reference.moe(alone, cfg_file, moe32)
        # the same layer in float32 on the masters: the routing's
        # arithmetic, with no rounding to hide behind
        exact = _rel_err(jax.jit(lambda u, lp: moe.moe_mlp(
            u, lp, engine.cfg.moe)[0])(alone[None], moe32)[0], want_moe)
    got_mix = jax.jit(shortconv.shortconv_mixer)(
        u[None], conv, seg[None])[0, start:]
    got_moe = jax.jit(lambda u, lp: moe.moe_mlp(u, lp, engine.cfg.moe)[0])(
        u[None, start:], experts)[0]
    mix, routed = _rel_err(got_mix, want_mix), _rel_err(got_moe, want_moe)
    blk = _rel_err(*expert_block(
        engine, reference, cfg_file, moe_kind, experts,
        engine.params["embedding"][row].astype(u.dtype), seg, start))
    out = {"tokens": int(mix.size), "behind": start,
           "mixer_median_rel_err": float(np.median(mix)),
           "mixer_head_rel_err": float(mix[:HEAD_TOKENS].mean()),
           "routed_tokens": int(routed.size),
           "moe_median_rel_err": float(np.median(routed))
           if routed.size else None,
           "moe_f32_median_rel_err": float(np.median(exact))
           if exact.size else None,
           "moe_f32_mean_rel_err": float(exact.mean()) if exact.size else None,
           "block_median_rel_err": float(np.median(blk))}
    out["ok"] = bool(
        out["mixer_median_rel_err"] <= MIXER_MEDIAN_REL_ERR
        and out["mixer_head_rel_err"] <= MIXER_HEAD_REL_ERR
        and routed.size > 0
        and out["moe_median_rel_err"] <= MOE_MEDIAN_REL_ERR
        and out["moe_f32_median_rel_err"] <= MOE_F32_MEDIAN_REL_ERR
        and out["moe_f32_mean_rel_err"] <= MOE_F32_MEAN_REL_ERR
        and out["block_median_rel_err"] <= BLOCK_MEDIAN_REL_ERR)
    return out


def expert_block(engine, reference, cfg_file: Dict[str, Any], kind: str,
                 copy, u, seg, start: int):
    """(got, want): what the first expert BLOCK — in the cut the attention
    block: q/k norms, RoPE, the grouped-head kernel, then the experts —
    adds to a stream ``u`` (the embedding as drawn: small beside what the
    block adds, so that nothing cancels): the program's
    ``transformer._block`` on the packed row (padded to whole lanes, so
    that a TPU runs the kernel the timed path runs) in the compute dtype,
    against the reference's ``block`` on the trajectory alone."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.models import transformer

    cfg = engine.cfg
    pad = -len(seg) % 128
    seg_p = jnp.pad(seg, (0, pad))[None]
    first = jnp.concatenate([jnp.ones(1, bool), seg[1:] != seg[:-1]])
    idx = jnp.arange(len(seg))
    pos = idx - jax.lax.cummax(jnp.where(first, idx, 0))
    pos_p = jnp.pad(pos, (0, pad))[None]
    h = jnp.pad(u, ((0, pad), (0, 0)))[None]

    def run(h, lp, seg, pos):
        ropes = transformer.rope_tables_by_kind(cfg, pos)
        return transformer._block(
            cfg, h, lp, {k: cs[0] for k, cs in ropes.items()},
            {k: cs[1] for k, cs in ropes.items()}, seg, pos, None, None,
            None, engine.attn_impl, kind=kind)[0] - h

    got = jax.jit(run)(h, copy, seg_p, pos_p)[0, start:len(seg)]
    masters = {k: w[0] for k, w in engine.params["layers"][kind].items()}
    alone = reference.f32(u[start:])
    with jax.default_matmul_precision("highest"):
        want = reference.block(alone, "full" if "wq" in masters else "conv",
                               False, cfg_file, masters) - alone
    return got, want


def conv_error(engine, cfg_file: Dict[str, Any], row, seg) -> Dict[str, Any]:
    """THE GATES AND THE TAPS ALONE, in float32, which no number in the
    compute dtype can see (a product rounded to bfloat16 before the three
    are summed is lost under the one rounding of ``y``): the first
    short-convolution block's ``shortconv.gated_conv`` on the packed row
    ``row`` / ``seg`` against the reference's ``C ⊙ taps(B ⊙ x)`` on the
    trajectory alone, both on the SAME float32 ``[B | C | x]`` (the
    reference's in-projection of the normed embedding), as the median
    over the trajectory's tokens of |difference| / |reference|."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.models import shortconv

    reference = importlib.import_module("benchmark." + cfg_file["reference"])
    kind = first_of(engine, dense=True)
    layer = {k: w[0] for k, w in engine.params["layers"][kind].items()}
    row, seg = jnp.asarray(row, jnp.int32), jnp.asarray(seg, jnp.int32)
    start = int(np.argmax(np.asarray(seg) == int(seg[-1])))
    with jax.default_matmul_precision("highest"):
        u = reference.rms(reference.f32(engine.params["embedding"][row]),
                          layer["ln1"], reference.eps_of(cfg_file))
        bcx = reference.mm(u, layer["sc_in"])
    Bg, Cg, x = jnp.split(bcx[start:], 3, axis=-1)
    want = Cg * reference.taps(Bg * x, layer["sc_conv"])
    got = jax.jit(shortconv.gated_conv)(
        bcx[None], reference.f32(layer["sc_conv"]), seg[None])[0, start:]
    rel = _rel_err(got, want)
    return {"tokens": int(rel.size), "median_rel_err": float(np.median(rel)),
            "max_rel_err": float(rel.max()),
            "ok": bool(rel.size > 0
                       and np.median(rel) <= CONV_MEDIAN_REL_ERR)}


def kernel_calls(cfg: Dict[str, Any], layouts: List[Tuple[str, str, Tuple]],
                 remat_plan: Dict[str, Dict[str, Any]],
                 ) -> Tuple[List[Dict[str, Any]], List[Dict[str, Any]]]:
    """(the convolutions some steps NEEDED, the attention calls they ran),
    for the rooflines, from those steps' micro-batches ``layouts``: each
    micro-batch of a grid ``RxL`` needs one doubly gated convolution a
    short-convolution block a pass — forward in the inference pass,
    forward and backward in the train pass (what a remat re-runs is the
    implementation's) — and runs one causal attention call an attention
    block a pass over its documents: forward; in the train pass the
    forward its backward re-runs where the grid's grad program keeps
    nothing of the kernel, and backward."""
    layers = shortconv_cost.layer_counts(cfg)
    convs: Dict[str, Dict[str, Any]] = {}
    attns: Dict[Tuple, Dict[str, Any]] = {}
    for which, key, docs in layouts:
        train = which == "train"
        R, L = (int(x) for x in key.split("x"))
        c = convs.setdefault(key, {
            "rows": R, "length": L, "channels": cfg["hidden_size"],
            "taps": cfg["conv_L_cache"], "fwd": 0, "bwd": 0})
        c["fwd"] += layers["conv"]
        c["bwd"] += layers["conv"] if train else 0
        refwd = train and remat_plan.get(key, {}).get("entry") == "full"
        a = attns.setdefault((key, docs), {
            "grid": key, "documents": list(docs), "fwd": 0, "bwd": 0})
        a["fwd"] += layers["full"] * (2 if refwd else 1)
        a["bwd"] += layers["full"] if train else 0
    return list(convs.values()), list(attns.values())


def main() -> int:
    spec = dl.load_spec()
    share = sharelib.set_up(spec, build_model, GAUGES)
    from areal_tpu.models import shortconv
    from areal_tpu.ops import attention

    sharelib.measure(share)
    engine, cfg_file = share.engine, spec["config"]
    sound = sharelib.steps_sound(share)
    routed = sharelib.routing(share, local_share(cfg_file))
    attn = attention.dispatch_counts()
    layers = shortconv_cost.layer_counts(cfg_file)
    # attention through the grouped-head causal kernel and nothing else
    # (no attention block in a cut shorter than the period: none traced)
    want = (set() if not layers["full"] else
            {"pallas"} if spec["platform"] == "tpu" else {"reference"})
    # every convolution at the configuration's channels and taps, one a
    # run of short-convolution blocks a program, on every packed grid
    runs = shortconv_cost.conv_runs(cfg_file)
    convs = shortconv.geometry_counts()
    conv_geometry = {"%dx%d/c%d/k%d" % g: c for g, c in convs.items()}
    geometry = (cfg_file["hidden_size"], cfg_file["conv_L_cache"])
    kernel_ok = (set(attn.get("train", {})) == want and bool(convs)
                 and all(g[2:] == geometry and c % runs == 0
                         for g, c in convs.items())
                 and share.every_grid <= {"%dx%d" % g[:2] for g in convs})
    # a trajectory behind another in its row, against the reference alone
    found = placed_later(share.ifaces, share.model, share.inf_spec,
                         share.samples, share.placements)
    if found is None:
        cmp, where = {"ok": False, "why": "no trajectory placed later"}, None
    else:
        got, toks, where = found
        cmp = compare_logprobs(
            got, reference_logprobs(engine.params, cfg_file, toks))
        row, seg = row_of(share.samples[where["batch"]], where)
        cmp["conv"] = conv_error(engine, cfg_file, row, seg)
        cmp["block"] = block_errors(engine, cfg_file, row, seg)
        cmp["ok"] = cmp["ok"] and cmp["conv"]["ok"] and cmp["block"]["ok"]
    correct = sound["ok"] and kernel_ok and routed["ok"] and cmp["ok"]

    conv_calls, attn_calls = kernel_calls(
        cfg_file, sharelib.traced_layouts(share), engine.remat_plan())
    sharelib.result(
        share, correct, sound, routed, shortconv_cost.share_params(cfg_file),
        # the convolutions as the program traced them, and the
        # convolutions and attention calls of the traced steps
        {"shortconv_geometry": conv_geometry,
         "shortconv_calls_traced": conv_calls,
         "lfm2_attn_calls_traced": attn_calls},
        f"attention={attn} reference={cmp} reference_of={where} "
        f"shortconv_geometry={conv_geometry}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
