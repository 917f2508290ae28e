"""What the reference tolerances of ``drivers/train_lfm2.py`` are FOR, on
the chip, by hand (not a cell, not run by the driver):

    python3 benchmark/check_limits_lfm2.py --seed <n>

Builds the cell ``lfm2-24b-a2b.train-toolcall-16k``'s model as its driver
does, takes the engine's logprobs of the longest trajectory the packer
placed behind another in its row and the first blocks' mixer and expert
layer on the same tokens, and compares them with ``reference_lfm2`` as it
is and with WRONG references, each of which should come out over at least
one of the driver's limits (``reference_lfm2.WRONG``):

 - ``silu_after_conv`` (the Mamba habit), ``no_b_gate``, ``no_c_gate``,
   ``taps_reversed``: the short convolution;
 - ``conv_products_in_bfloat16``: each ``w_j ⊙ z`` rounded to bfloat16
   before the three are summed (the program keeps them in float32);
 - ``bias_left_out_of_choice``, ``bias_added_to_gates``,
   ``gates_not_renormalised``, ``softmax_for_sigmoid``: the expert layer;
 - ``no_qk_norm``: attention;
 - ``matmuls_in_float8``: the reference computed in float8_e4m3, the
   nearest precision below the configuration's bfloat16 — both operands of
   every matrix product against a weight rounded to it;
 - ``taps_cross_document_start``: the short-convolution blocks run over
   the trajectory's packed row (the documents ahead of it in its row, then
   itself) as if it were one document (taps carried across the
   boundaries; attention and positions still by document).

One seed a process (the engine holds most of the chip); prints one JSON
line and appends it to ``chiprun_out/check_limits_lfm2.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness, traffic  # noqa: E402

CELL = "lfm2-24b-a2b.train-toolcall-16k"


def logprobs_with_taps_across(ref, params, cfg, docs):
    """Logprobs of the LAST of ``docs`` (token arrays, in row order) under
    a model whose short convolutions never stop at a document start: they
    see the documents as one."""
    import jax
    import jax.numpy as jnp

    ends = np.cumsum([len(d) for d in docs])
    bounds = list(zip([0] + list(ends[:-1]), ends))
    toks = jnp.asarray(np.concatenate(docs), jnp.int32)
    eps = ref.eps_of(cfg)
    h = ref.f32(params["embedding"][toks])
    for kind, dense, lp in ref.layers_of(params, cfg):
        u = ref.rms(h, lp["ln1"], eps)
        if kind == "full":
            mix = jnp.concatenate(
                [ref.attention(u[a:b], cfg, lp) for a, b in bounds], 0)
        else:
            mix = ref.shortconv(u, cfg, lp)
        h = h + mix
        u = ref.rms(h, lp["ln2"], eps)
        h = h + (ref.swiglu(u, lp["w_gate"], lp["w_up"], lp["w_down"])
                 if dense else ref.moe(u, cfg, lp))
    a, b = bounds[-1]
    lg = ref.mm(ref.rms(h[a:b], params["final_ln"], eps),
                ref.head_of(params))
    lp = jax.nn.log_softmax(lg[:-1], -1)
    return np.asarray(jnp.take_along_axis(lp, toks[a + 1:b, None], -1)[:, 0])


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--platform", default="tpu")  # cpu: a rehearsal
    args = ap.parse_args()
    seed = args.seed
    import jax

    from areal_tpu.base.compile_watch import enable_compilation_cache
    from benchmark import reference_lfm2 as ref
    from benchmark.drivers import train_lfm2 as drv
    from benchmark.drivers.train import to_sample
    from benchmark.drivers.train_ep import build_experiment
    from benchmark.drivers.train_hybrid import Placements

    enable_compilation_cache()
    out = os.path.join(harness.OUT_ROOT, f"check-limits-{seed}")
    os.makedirs(out, exist_ok=True)
    if args.platform == "tpu":
        spec = {**harness.resolve_cell(CELL), "workload": CELL, "seed": seed,
                "out": out, "t0": time.time(), "platform": "tpu", "trace": 0}
    else:  # the driver's toy size
        from benchmark import rehearse

        spec = {**rehearse.tiny_spec(CELL, 0, 8.0), "seed": seed, "out": out}
    exp = build_experiment(spec)
    model, ifaces, _ = drv.build_model(spec, exp)
    engine = model.module
    placements = Placements(engine)
    t, cfg = spec["traffic"], spec["config"]
    samples = []
    for i, raw in enumerate(traffic.make_train_batches(
            t["shape"], t["n_batches"], exp.dataset.train_bs_n_seqs,
            exp.group_size, seed, cfg["vocab_size"])):
        raw["packed_logprobs"] = np.zeros(len(raw["packed_input_ids"]),
                                          np.float32)
        samples.append(to_sample(raw, f"b{i}"))
    got, toks, where = drv.placed_later(
        ifaces, model, exp.actor_inf.mb_spec, samples, placements)
    params = engine.params
    line = {"seed": seed, "where": where,
            "limits": {"max": drv.LOGPROB_MAX_ERR,
                       "mean": drv.LOGPROB_MEAN_ERR,
                       "head_mean": drv.LOGPROB_HEAD_ERR,
                       "mixer_median_rel": drv.MIXER_MEDIAN_REL_ERR,
                       "mixer_head_rel": drv.MIXER_HEAD_REL_ERR,
                       "moe_median_rel": drv.MOE_MEDIAN_REL_ERR,
                       "moe_f32_median_rel": drv.MOE_F32_MEDIAN_REL_ERR,
                       "moe_f32_mean_rel": drv.MOE_F32_MEAN_REL_ERR,
                       "block_median_rel": drv.BLOCK_MEDIAN_REL_ERR,
                       "conv_median_rel": drv.CONV_MEDIAN_REL_ERR}}

    def against(wrong=ref.NONE):
        with jax.default_matmul_precision("highest"):
            want = np.asarray(ref.token_logprobs(params, cfg, toks, wrong))
        return drv.compare_logprobs(got, want)

    sample = samples[where["batch"]]
    row, seg = drv.row_of(sample, where)
    # the reference's pieces the blocks' comparisons call, and how many
    # arguments each takes in front of ``wrong``
    patched = {"shortconv": 3, "moe": 3, "taps": 2, "attention": 3}

    def with_blocks(cmp, wrong=ref.NONE):
        """``cmp`` with the first blocks' own comparisons, the reference's
        pieces made ``wrong``."""
        real = {name: getattr(ref, name) for name in patched}
        if wrong:
            for name, n in patched.items():
                setattr(ref, name,
                        lambda *a, _f=real[name], _n=n: _f(*a[:_n], wrong))
        try:
            cmp["conv"] = drv.conv_error(engine, cfg, row, seg)
            cmp["block"] = drv.block_errors(engine, cfg, row, seg)
        finally:
            for name in patched:
                setattr(ref, name, real[name])
        cmp["ok"] = cmp["ok"] and cmp["conv"]["ok"] and cmp["block"]["ok"]
        return cmp

    line["as_published"] = with_blocks(against())
    for name in ref.WRONG:
        line[name] = with_blocks(against(frozenset({name})),
                                 frozenset({name}))

    # the documents ahead of it in its row, then itself: taps across the
    # boundaries — in the logprobs, and in the first block's mixer (the
    # reference's mixer over the whole row as one document, its last part
    # compared)
    lens = [int(n) for n in sample.total_lens("packed_input_ids")]
    ids = np.asarray(sample.data["packed_input_ids"])
    docs = [ids[sum(lens[:j]):sum(lens[:j + 1])]
            for j in where["ahead_in_row"] + [where["trajectory"]]]
    with jax.default_matmul_precision("highest"):
        across = logprobs_with_taps_across(ref, params, cfg, docs)
    line["taps_cross_document_start"] = drv.compare_logprobs(got, across)
    real_conv, behind = ref.shortconv, len(row) - where["tokens"]
    u_row = {}

    def conv_over_the_row(u, cfg_, lp, wrong=ref.NONE):
        # block_errors hands the trajectory's part: take the row's instead
        return real_conv(u_row["u"], cfg_, lp, wrong)[behind:]

    real_rms = ref.rms

    def keep_row(x, w, eps):
        out = real_rms(x, w, eps)
        if out.shape[0] == len(row):
            u_row["u"] = ref.f32(out.astype("bfloat16")
                                 if args.platform == "tpu" else out)
        return out

    ref.shortconv, ref.rms = conv_over_the_row, keep_row
    try:
        line["taps_cross_document_start"]["block"] = drv.block_errors(
            engine, cfg, row, seg)
    finally:
        ref.shortconv, ref.rms = real_conv, real_rms
    line["taps_cross_document_start"]["ok"] = (
        line["taps_cross_document_start"]["ok"]
        and line["taps_cross_document_start"]["block"]["ok"])

    line["passes_every_limit"] = sorted(
        k for k, v in line.items() if isinstance(v, dict) and v.get("ok")
        and k != "as_published")
    print(json.dumps(line), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/check_limits_lfm2.jsonl", "a") as f:
        f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
