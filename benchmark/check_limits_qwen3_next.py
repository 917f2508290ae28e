"""What the reference tolerances of ``drivers/train_qwen3_next.py`` are FOR,
on the chip, by hand (not a cell, not run by the driver):

    python3 benchmark/check_limits_qwen3_next.py --seed <n>

Builds the cell ``qwen3-next-80b-a3b.train-longdoc-16k``'s model as its
driver does, takes the engine's logprobs of the longest trajectory the
packer placed behind another in its row and the first block's rule on
the same tokens, and compares them with ``reference_qwen3_next`` as it is
and with WRONG references, each of which should come out over at least
one of the driver's limits (``reference_qwen3_next.WRONG``):

 - ``state_in_bfloat16``: the rule's state rounded to bfloat16 after every
   token (a drawn ``A_log`` forgets within a few tokens, so no logprob
   moves: the rule's own limit, ``rule_error``, is what refuses it);
 - ``beta_left_at_1``, ``no_l2_norm_of_q_and_k``: the rule's inputs;
 - ``rope_on_all_dims``: RoPE on all 256 dims of a head, not the first 64;
 - ``norm_weight_without_1_plus``: every zero-centred weight read plainly;
 - ``no_shared_expert_gate``, ``gates_not_renormalised``: the expert layer;
 - ``matmuls_in_float8``: the reference computed in float8_e4m3, the
   nearest precision below the configuration's bfloat16 — both operands of
   every matrix product against a weight rounded to it;
 - ``reset_left_off``: the Gated DeltaNet blocks run over the trajectory's
   packed row (the documents ahead of it in its row, then itself) as if it
   were one document (state and convolution carried across the
   boundaries; attention and positions still by document).

One seed a process (the engine holds most of the chip); prints one JSON
line and appends it to ``chiprun_out/check_limits_qwen3_next.jsonl``.
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

CELL = "qwen3-next-80b-a3b.train-longdoc-16k"


def logprobs_without_reset(ref, params, cfg, docs, n_ref: int):
    """Logprobs of the LAST of ``docs`` (token arrays, in row order), its
    first ``n_ref`` tokens, under a model whose Gated DeltaNet blocks never
    reset: they see the documents as one."""
    import jax
    import jax.numpy as jnp

    docs = list(docs[:-1]) + [docs[-1][:n_ref]]
    ends = np.cumsum([len(d) for d in docs])
    bounds = list(zip([0] + list(ends[:-1]), ends))
    toks = jnp.asarray(np.concatenate(docs), jnp.int32)
    eps = ref.eps_of(cfg)
    h = ref.f32(params["embedding"][toks])
    for kind, lp in ref.layers_of(params, cfg):
        u = ref.rms(h, lp["ln1"], eps)
        if kind == "full":
            mix = jnp.concatenate(
                [ref.attention(u[a:b], cfg, lp) for a, b in bounds], 0)
        else:
            mix = ref.gdn(u, cfg, lp)
        h = h + mix
        h = h + ref.moe(ref.rms(h, lp["ln2"], eps), cfg, lp)
    a, b = bounds[-1]
    return np.asarray(ref.logprobs_of(ref.head(params, cfg, h[a:b]),
                                      toks[a:b]))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--platform", default="tpu")  # cpu: a rehearsal
    args = ap.parse_args()
    seed = args.seed
    import jax

    from areal_tpu.base.compile_watch import enable_compilation_cache
    from benchmark import reference_qwen3_next as ref
    from benchmark.drivers import train_qwen3_next as drv
    from benchmark.drivers.train import to_sample
    from benchmark.drivers.train_ep import build_experiment

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
    placements = drv.Placements(engine)
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
                       "rule_median_rel": drv.RULE_MEDIAN_REL_ERR}}

    def against(wrong=ref.NONE):
        with jax.default_matmul_precision("highest"):
            want = np.asarray(ref.token_logprobs(params, cfg, toks, wrong))
        return drv.compare_logprobs(got, want)

    sample = samples[where["batch"]]
    row, seg = drv.row_of(sample, where)
    # the reference's pieces the rule's and the block's comparisons call,
    # and how many arguments each takes in front of ``wrong``
    patched = {"delta_rule": 5, "gdn": 3, "moe": 3, "routed": 3}

    def with_blocks(cmp, wrong=ref.NONE):
        """``cmp`` with the rule's and the first block's own comparisons,
        the reference's pieces made ``wrong``."""
        real = {name: getattr(ref, name) for name in patched}
        if wrong:
            for name, n in patched.items():
                setattr(ref, name,
                        lambda *a, _f=real[name], _n=n: _f(*a[:_n], wrong))
        try:
            cmp["rule"] = drv.rule_error(engine, cfg, toks)
            cmp["block"] = drv.block_errors(engine, cfg, row, seg)
        finally:
            for name in patched:
                setattr(ref, name, real[name])
        cmp["ok"] = cmp["ok"] and cmp["rule"]["ok"] and cmp["block"]["ok"]
        return cmp

    line["as_published"] = with_blocks(against())
    for name in ref.WRONG:
        line[name] = with_blocks(against(frozenset({name})),
                                 frozenset({name}))

    # the documents ahead of it in its row, then itself: no reset — in the
    # logprobs, and in the first block's mixer (the reference's mixer over
    # the whole row as one document, its last part compared)
    lens = [int(n) for n in sample.total_lens("packed_input_ids")]
    ids = np.asarray(sample.data["packed_input_ids"])
    docs = [ids[sum(lens[:j]):sum(lens[:j + 1])]
            for j in where["ahead_in_row"] + [where["trajectory"]]]
    with jax.default_matmul_precision("highest"):
        no_reset = logprobs_without_reset(ref, params, cfg, docs,
                                          where["tokens"])
    line["reset_left_off"] = drv.compare_logprobs(got, no_reset)
    real_gdn, behind = ref.gdn, len(row) - where["tokens"]
    u_row = {}

    def gdn_over_the_row(u, cfg_, lp, wrong=ref.NONE):
        # block_errors hands the trajectory's part: take the row's instead
        return real_gdn(u_row["u"], cfg_, lp, wrong)[behind:]

    real_rms = ref.rms

    def keep_row(x, w, eps, wrong=ref.NONE):
        out = real_rms(x, w, eps, wrong)
        if out.shape[0] == len(row):
            u_row["u"] = ref.f32(out.astype("bfloat16")
                                 if args.platform == "tpu" else out)
        return out

    ref.gdn, ref.rms = gdn_over_the_row, keep_row
    try:
        line["reset_left_off"]["block"] = drv.block_errors(
            engine, cfg, row, seg)
    finally:
        ref.gdn, ref.rms = real_gdn, real_rms
    line["reset_left_off"]["ok"] = (line["reset_left_off"]["ok"]
                                    and line["reset_left_off"]["block"]["ok"])

    line["passes_every_limit"] = sorted(
        k for k, v in line.items() if isinstance(v, dict) and v.get("ok")
        and k != "as_published")
    print(json.dumps(line), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/check_limits_qwen3_next.jsonl", "a") as f:
        f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
