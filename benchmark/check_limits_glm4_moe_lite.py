"""What the reference tolerances of ``drivers/train_glm4_moe_lite.py`` are
FOR, on the chip, at the published widths and the cell's timed sizes: takes
the engine's logprobs (the timed path: bfloat16, the grouped-head causal
kernel at heads of 256, sorted grouped GEMMs over the held experts) of ALL
tokens of the batches' longest trajectory and of the longest one that the
packer placed behind another in its row, with the first block's attention
branch and the first expert layer on the second one's tokens, and compares
them with ``reference_glm4_moe_lite`` as it is and — on the trajectory
behind another — with WRONG references, each of which should come out over
at least one of the driver's limits (``reference_glm4_moe_lite.WRONG``):

 - the rotary part: ``no_rope_on_k_r``, ``rope_on_first_dims`` (the first
   64 dims of a head turned, not the last), ``k_r_per_head`` (every head
   its own rotary key);
 - the latents: ``kv_norm_over_all`` (``kv_a_layernorm`` over all 576),
   ``no_q_latent_norm``, ``no_kv_latent_norm``, ``kv_b_split_v_first``;
 - ``scale_by_nope_dim``: 192^-0.5 in place of 256^-0.5;
 - the router: ``bias_left_out_of_choice``, ``bias_added_to_gates``,
   ``gates_not_renormalised``, ``no_routed_scaling`` (the 1.8 left out),
   ``scaling_on_shared_too``, ``no_shared_expert``, ``softmax_for_sigmoid``;
 - ``matmuls_in_float8``: the reference computed in float8_e4m3, the
   nearest precision below the configuration's bfloat16;
 - ``attention_across_document_start``: no flag of the reference — its
   attention run over the trajectory's ROW as one document.

Not a control: "the latents rounded to bfloat16 before their norms" — the
program's latents ARE bfloat16 (a matmul's output in the compute dtype) and
their norms compute in float32 from them, so that model is the program.

    chiprun -- python3 benchmark/check_limits_glm4_moe_lite.py --seed 11

prints one JSON line (appended to ``chiprun_out/check_limits_glm4.jsonl``);
``--platform cpu`` rehearses it at the driver's toy size.
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

CELL = "glm-4.7-flash.train-swe-agent-16k"
ACROSS = "attention_across_document_start"


def logprobs_with_attention_across(ref, params, cfg, docs):
    """Logprobs of the LAST of ``docs`` (token arrays, in row order) under
    a model whose attention never stops at a document start: it sees the
    documents as one (positions running on)."""
    import jax
    import jax.numpy as jnp

    toks = jnp.asarray(np.concatenate(docs), jnp.int32)
    a = len(toks) - len(docs[-1])
    h = ref.hidden(params, cfg, toks)
    lg = ref.mm(ref.rms(h[a:], params["final_ln"], ref.eps_of(cfg)),
                params["lm_head"])
    lp = jax.nn.log_softmax(lg[:-1], -1)
    return np.asarray(jnp.take_along_axis(lp, toks[a + 1:, None], -1)[:, 0])


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--platform", default="tpu")  # cpu: a rehearsal
    args = ap.parse_args()
    seed = args.seed
    import jax

    from areal_tpu.base.compile_watch import enable_compilation_cache
    from benchmark import reference_glm4_moe_lite as ref
    from benchmark.drivers import train_glm4_moe_lite as drv
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
    found = drv.placed(ifaces, model, exp.actor_inf.mb_spec, samples,
                       placements)
    params = engine.params
    line = {"seed": seed,
            "limits": {"max": drv.LOGPROB_MAX_ERR,
                       "mean": drv.LOGPROB_MEAN_ERR,
                       "head_mean": drv.LOGPROB_HEAD_ERR,
                       "attn_median_rel": drv.ATTN_MEDIAN_REL_ERR,
                       "attn_head_rel": drv.ATTN_HEAD_REL_ERR,
                       "moe_median_rel": drv.MOE_MEDIAN_REL_ERR,
                       "moe_f32_median_rel": drv.MOE_F32_MEDIAN_REL_ERR,
                       "moe_f32_mean_rel": drv.MOE_F32_MEAN_REL_ERR}}
    # the longest trajectory, as published
    got1, toks1, where1 = found["first"]
    with jax.default_matmul_precision("highest"):
        line["first_as_published"] = {**drv.compare_logprobs(
            got1, np.asarray(ref.token_logprobs(params, cfg, toks1))),
            "where": where1}
        # ... and against the nearest precision below the configuration's
        line["first_matmuls_in_float8"] = drv.compare_logprobs(
            got1, np.asarray(ref.token_logprobs(
                params, cfg, toks1, frozenset({"matmuls_in_float8"}))))
    got, toks, where = found["later"]
    line["where"] = where

    def against(wrong=ref.NONE):
        with jax.default_matmul_precision("highest"):
            want = np.asarray(ref.token_logprobs(params, cfg, toks, wrong))
        return drv.compare_logprobs(got, want)

    sample = samples[where["batch"]]
    row, seg = drv.row_of(sample, where)
    # the reference's pieces the blocks' comparisons call, and how many
    # arguments each takes in front of ``wrong``
    patched = {"attention": 3, "moe": 3}

    def with_blocks(cmp, wrong=ref.NONE):
        """``cmp`` with the first blocks' own comparisons, the reference's
        pieces made ``wrong``."""
        real = {name: getattr(ref, name) for name in patched}
        if wrong:
            for name, n in patched.items():
                setattr(ref, name,
                        lambda *a, _f=real[name], _n=n: _f(*a[:_n], wrong))
        try:
            cmp["block"] = drv.block_errors(engine, cfg, row, seg)
        finally:
            for name in patched:
                setattr(ref, name, real[name])
        cmp["ok"] = cmp["ok"] and cmp["block"]["ok"]
        return cmp

    line["as_published"] = with_blocks(against())
    for name in ref.WRONG:
        line[name] = with_blocks(against(frozenset({name})),
                                 frozenset({name}))

    # the document ahead of it in its row, then itself: attention across
    # the boundary — in the logprobs, and in the first block's attention
    # branch (the reference's over the whole row as one document, its last
    # part compared)
    lens = [int(n) for n in sample.total_lens("packed_input_ids")]
    ids = np.asarray(sample.data["packed_input_ids"])
    docs = [ids[sum(lens[:j]):sum(lens[:j + 1])]
            for j in where["ahead_in_row"] + [where["trajectory"]]]
    with jax.default_matmul_precision("highest"):
        across = logprobs_with_attention_across(ref, params, cfg, docs)
    line[ACROSS] = drv.compare_logprobs(got, across)
    real_attn, real_rms = ref.attention, ref.rms
    behind, u_row = len(row) - where["tokens"], {}

    def keep_row(x, w, eps):
        y = real_rms(x, w, eps)
        if y.shape[0] == len(row):
            u_row["u"] = y
        return y

    def attention_over_the_row(u, cfg_, lp, wrong=ref.NONE):
        # block_errors hands the trajectory's part: take the row's instead
        return real_attn(u_row["u"], cfg_, lp, wrong)[behind:]

    ref.attention, ref.rms = attention_over_the_row, keep_row
    try:
        line[ACROSS]["block"] = drv.block_errors(engine, cfg, row, seg)
    finally:
        ref.attention, ref.rms = real_attn, real_rms
    line[ACROSS]["ok"] = line[ACROSS]["ok"] and line[ACROSS]["block"]["ok"]

    line["passes_every_limit"] = sorted(
        k for k, v in line.items() if isinstance(v, dict) and v.get("ok")
        and k not in ("as_published", "first_as_published", "limits"))
    print(json.dumps(line), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/check_limits_glm4.jsonl", "a") as f:
        f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
