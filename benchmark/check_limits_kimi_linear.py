"""What the reference tolerances of ``drivers/train_kimi_linear.py`` are FOR,
on the chip, at the published widths and the cell's timed sizes: takes the
engine's logprobs (the timed path: bfloat16, the kernel pair ``kda_rule_fwd``
/ ``kda_rule_bwd``, the grouped-head causal kernel at a key of 192 over a
value of 128, sorted grouped GEMMs over the held experts) of ALL tokens of
the batches' longest trajectory and of the longest one that the packer
placed behind another in its row, with the first KDA mixer, the attention
branch and the first expert layer on the second one's tokens, and compares
them with ``reference_kimi_linear`` as it is and — on the trajectory behind
another — with WRONG references, each of which should come out over at
least one of the driver's limits (``reference_kimi_linear.WRONG``):

 - the rule: ``decay_averaged_over_channels`` (one decay a head: a Gated
   DeltaNet rule in KDA's place), ``delta_before_decay``,
   ``state_bf16_each_chunk`` (the carried state rounded to bfloat16 every
   64 tokens), ``beta_left_out``, ``no_l2_norm``,
   ``A_log_per_channel_read_as_zero``, ``no_dt_bias``,
   ``conv_taps_reversed``; ``silu_output_gate`` (Gated DeltaNet's gate for
   the sigmoid);
 - latent attention: ``rope_on_latent_attention`` (the last 64 dims
   rotated, as GLM rotates them), ``kv_norm_over_all``,
   ``scale_by_nope_dim``, ``kv_b_split_v_first``;
 - the router: ``bias_left_out_of_choice``, ``gates_not_renormalised``,
   ``no_routed_scaling`` (the 2.446 left out), ``scaling_on_shared_too``,
   ``no_shared_expert``, ``softmax_for_sigmoid``;
 - ``matmuls_in_float8``: the reference computed in float8_e4m3, the
   nearest precision below the configuration's bfloat16;
 - ``no_reset_at_document_start``: no flag of the reference — its state,
   taps and attention run over the trajectory's ROW as one document.

    chiprun -- python3 benchmark/check_limits_kimi_linear.py --seed 11

prints one JSON line (appended to ``chiprun_out/check_limits_kimi.jsonl``);
``--platform cpu`` rehearses it at the driver's toy size (a cut to two
blocks: no attention block there).
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
from benchmark.check_limits_glm4_moe_lite import (  # noqa: E402
    logprobs_with_attention_across as logprobs_across,
)

CELL = "kimi-linear-48b-a3b.train-math-cot-16k"
ACROSS = "no_reset_at_document_start"
# the reference's pieces the blocks' comparisons call, and how many
# arguments each takes in front of ``wrong``
PATCHED = {"kda": 3, "attention": 3, "moe": 3, "delta_rule": 5}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--platform", default="tpu")  # cpu: a rehearsal
    ap.add_argument("--only", nargs="*", default=None)  # of the controls
    args = ap.parse_args()
    seed = args.seed
    import jax

    from areal_tpu.base.compile_watch import enable_compilation_cache
    from benchmark import reference_kimi_linear as ref
    from benchmark.drivers import train_kimi_linear as drv
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
    line = {"seed": seed, "limits": {
        name: getattr(drv, name) for name in dir(drv)
        if name.endswith("_ERR")}}
    got1, toks1, where1 = found["first"]
    with jax.default_matmul_precision("highest"):
        line["first_as_published"] = {**drv.compare_logprobs(
            got1, np.asarray(ref.token_logprobs(params, cfg, toks1))),
            "where": where1}
    got, toks, where = found["later"]
    line["where"] = where
    sample = samples[where["batch"]]
    row, seg = drv.row_of(sample, where)

    def against(wrong=ref.NONE):
        with jax.default_matmul_precision("highest"):
            want = np.asarray(ref.token_logprobs(params, cfg, toks, wrong))
        return drv.compare_logprobs(got, want)

    def with_blocks(cmp, wrong=ref.NONE, across=False):
        """``cmp`` with the blocks' own comparisons, the reference's pieces
        made ``wrong``."""
        real = {name: getattr(ref, name) for name in PATCHED}
        if wrong:
            for name, n in PATCHED.items():
                setattr(ref, name,
                        lambda *a, _f=real[name], _n=n: _f(*a[:_n], wrong))
        try:
            cmp["block"] = drv.block_errors(engine, cfg, row, seg, across)
            cmp["rule"] = drv.rule_error(engine, cfg, toks)
        finally:
            for name in PATCHED:
                setattr(ref, name, real[name])
        cmp["ok"] = cmp["ok"] and cmp["block"]["ok"] and cmp["rule"]["ok"]
        return cmp

    line["as_published"] = with_blocks(against())
    for name in ref.WRONG:
        if args.only is None or name in args.only:
            line[name] = with_blocks(against(frozenset({name})),
                                     frozenset({name}))
    # the documents ahead of it in its row, then itself, as ONE document
    lens = [int(n) for n in sample.total_lens("packed_input_ids")]
    ids = np.asarray(sample.data["packed_input_ids"])
    docs = [ids[sum(lens[:j]):sum(lens[:j + 1])]
            for j in where["ahead_in_row"] + [where["trajectory"]]]
    with jax.default_matmul_precision("highest"):
        across = logprobs_across(ref, params, cfg, docs)
    line[ACROSS] = with_blocks(drv.compare_logprobs(got, across), across=True)
    line["passes_every_limit"] = sorted(
        k for k, v in line.items() if isinstance(v, dict) and v.get("ok")
        and k not in ("as_published", "first_as_published", "limits"))
    print(json.dumps(line), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/check_limits_kimi.jsonl", "a") as f:
        f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
