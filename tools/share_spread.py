#!/usr/bin/env python3
"""How far the held experts' share of the routed (token, expert) pairs
swings with the seed in a share cell: weights and token ids drawn as the
cell draws them (benchmark/weights.make_params, traffic.make_train_batches),
one inference forward of the first 2 x 8,192 tokens of the first batch a
seed, ``local_rows / routed_rows`` summed over the expert layers, over the
even router's share. The measurement behind a driver's ``LOCAL_SHARE_BAND``
(benchmark/drivers/train_kimi_linear.py; PERF.md section 6, PR 63;
``--workload keye-vl-2.0-30b-a3b.train-video-reason-16k``: drivers/
train_keye_vl2.py, PERF.md section 2, PR 66).

    chiprun -- python tools/share_spread.py <seed> <seed> ...   # ~4 s a seed
    JAX_PLATFORMS=cpu python tools/share_spread.py --tiny 1 2 3  # rehearsal
"""
import argparse
import dataclasses
import importlib
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("seeds", type=int, nargs="+")
    ap.add_argument("--workload",
                    default="kimi-linear-48b-a3b.train-math-cot-16k")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from areal_tpu.models import transformer
    from benchmark import harness, traffic, weights

    r = harness.resolve_cell(args.workload)
    # what the cell's driver draws its embedding at, over the program's
    embed_scale = getattr(importlib.import_module(
        "benchmark.drivers." + r["traffic"]["driver"]), "EMBED_SCALE", 1.0)
    cfg_file, shape = dict(r["config"]), r["traffic"]["shape"]
    rows, T = 2, 8192
    if args.tiny:
        cfg_file.update(num_hidden_layers=2, intermediate_size=64,
                        hidden_size=cfg_file["num_attention_heads"] * 8,
                        vocab_size=512)
        rows, T = 1, 256
    cfg = weights.model_config(cfg_file)
    compute = dataclasses.replace(cfg, dtype="bfloat16")
    even = cfg_file["num_experts"] / cfg_file["num_routed_experts"]

    @jax.jit
    def routed(params, tokens, positions, seg):
        p = jax.tree.map(
            lambda a: a.astype(jnp.bfloat16)
            if a.dtype == jnp.float32 and a.ndim > 1 else a, params)
        return transformer.forward(
            p, compute, tokens, positions, seg, return_kv=False,
            return_aux=True, return_hidden=True)[2]

    lines = []
    for seed in args.seeds:
        began = time.time()
        b = traffic.make_train_batches(shape, 1, 4, 4, seed,
                                       cfg_file["vocab_size"])[0]
        lens = b["seqlens"]
        ids = b["packed_input_ids"]
        seg = np.concatenate([np.full(n, i + 1, np.int32)
                              for i, n in enumerate(lens)])
        pos = np.concatenate([np.arange(n, dtype=np.int32) for n in lens])
        short = max(rows * T - len(ids), 0)  # padding: segment 0
        tok, seg, pos = (
            jnp.asarray(np.pad(a, (0, short))[:rows * T].reshape(rows, T))
            for a in (ids, seg, pos))
        params = weights.make_params(cfg, seed)
        params["embedding"] = params["embedding"] * embed_scale
        aux = jax.device_get(routed(params, tok, pos, seg))
        del params
        share = float(aux["local_rows"] / aux["routed_rows"])
        lines.append({"workload": args.workload, "seed": seed,
                      "share": share, "x_even": share / even,
                      "load_ratio": float(aux["expert_load_ratio"]),
                      "s": round(time.time() - began, 1)})
        print(json.dumps(lines[-1]), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/share_spread.jsonl", "a") as f:
        f.writelines(json.dumps(line) + "\n" for line in lines)
    x = np.array([line["x_even"] for line in lines])
    print(json.dumps({
        "n": len(x), "mean": float(x.mean()),
        "std": float(x.std(ddof=1)) if len(x) > 1 else 0.0,
        "min": float(x.min()), "max": float(x.max())}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
