"""What the reference tolerances of ``drivers/train_keye_vl2.py`` are FOR,
on the chip, at the published widths and the cell's timed sizes: takes the
program's logprobs of the cell's shorter trajectory BEHIND another
document on a row of the driver's own packing (the model's own forward on
the engine's compute-dtype weights: bfloat16, the kernels ``dsa_select`` /
``dsa_attend_*``, sorted grouped GEMMs over the held experts), with the
first block's attention branch, its expert layer and the selection's
overlap on that trajectory, and compares them with ``reference_keye_vl2``
as it is and with WRONG references, each of which should come out over at
least one of the driver's limits (``reference_keye_vl2.WRONG``):

 - the indexer: ``relu_left_out``, ``weights_ones``, ``no_key_layernorm``,
   ``no_indexer_rope``, ``indexer_in_float8`` (qI, kI in float8_e4m3);
 - the selection: ``recent_instead_of_best`` (the 2,048 most recent keys:
   a window), ``topk_halved``, ``no_selection`` (full causal attention);
 - attention: ``attention_in_float8`` (q, k, v in float8_e4m3),
   ``no_qk_norm``; the router: ``gates_not_renormalised``;
 - ``matmuls_in_float8``: the reference computed in float8_e4m3, the
   nearest precision below the configuration's bfloat16;
 - ``no_reset_at_document_start``: no flag of the reference — its
   selection and attention run over the trajectory's ROW as one document.

    chiprun -- python3 benchmark/check_limits_keye_vl2.py --seed 11

prints one JSON line (appended to ``chiprun_out/check_limits_keye.jsonl``);
``--platform cpu`` rehearses it at the driver's toy size (rows shorter
than the top-k: the selection's controls move nothing there).
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

CELL = "keye-vl-2.0-30b-a3b.train-video-reason-16k"
ACROSS = "no_reset_at_document_start"
# the reference's pieces the blocks' comparisons call, and how many
# arguments each takes in front of ``wrong``
PATCHED = {"attention": 3, "moe": 3, "selection": 3, "gates": 3}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--platform", default="tpu")  # cpu: a rehearsal
    ap.add_argument("--only", nargs="*", default=None)  # of the controls
    args = ap.parse_args()
    seed = args.seed
    import jax

    from areal_tpu.base.compile_watch import enable_compilation_cache
    from benchmark import reference_keye_vl2 as ref
    from benchmark.drivers import train_keye_vl2 as drv
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
    model, _, _ = drv.build_model(spec, exp)
    engine = model.module
    t, cfg = spec["traffic"], spec["config"]
    samples = []
    for i, raw in enumerate(traffic.make_train_batches(
            t["shape"], t["n_batches"], exp.dataset.train_bs_n_seqs,
            exp.group_size, seed, cfg["vocab_size"])):
        raw["packed_logprobs"] = np.zeros(len(raw["packed_input_ids"]),
                                          np.float32)
        samples.append(to_sample(raw, f"b{i}"))
    sample = min(samples, key=lambda s: int(
        s.total_lens("packed_input_ids")[0]))
    row, seg = drv.packed_row(sample, drv.PACKED_AHEAD)
    start = int(np.argmax(seg == seg[-1]))
    toks = row[start:]
    got = drv.packed_logprobs(engine, row, seg)[start:len(seg) - 1]
    params = engine.params
    line = {"seed": seed, "tokens": int(len(toks)), "behind": start,
            "limits": {name: getattr(drv, name) for name in dir(drv)
                       if name.endswith("_ERR") or name.endswith("_OVERLAP")}}

    def against(wrong=ref.NONE, tokens=toks, tail=None):
        with jax.default_matmul_precision("highest"):
            want = np.asarray(ref.token_logprobs(params, cfg, tokens, wrong))
        return drv.compare_logprobs(got, want if tail is None else want[tail:])

    def with_blocks(cmp, wrong=ref.NONE):
        """``cmp`` with the first block's own comparisons, the reference's
        pieces made ``wrong``."""
        real = {name: getattr(ref, name) for name in PATCHED}
        if wrong:
            for name, n in PATCHED.items():
                setattr(ref, name,
                        lambda *a, _f=real[name], _n=n: _f(*a[:_n], wrong))
        try:
            cmp["block"] = drv.block_errors(engine, cfg, row, seg)
        finally:
            for name in PATCHED:
                setattr(ref, name, real[name])
        cmp["ok"] = cmp["ok"] and cmp["block"]["ok"]
        return cmp

    line["as_published"] = with_blocks(against())
    for name in ref.WRONG:
        if args.only is None or name in args.only:
            line[name] = with_blocks(against(frozenset({name})),
                                     frozenset({name}))
    # the document ahead of it in its row, then itself, as ONE document
    # (the logprobs alone: the blocks' references run on the trajectory)
    if args.only is None or ACROSS in args.only:
        line[ACROSS] = against(tokens=row, tail=start)
    line["passes_every_limit"] = sorted(
        k for k, v in line.items() if isinstance(v, dict) and v.get("ok")
        and k not in ("as_published", "limits"))
    print(json.dumps(line), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/check_limits_keye.jsonl", "a") as f:
        f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
