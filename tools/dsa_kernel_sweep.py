#!/usr/bin/env python3
"""Attention under a learned selection (ops/pallas/sparse_attention.py,
models/dsa.py) on the chip: the compiled kernels against the XLA form of
the same entry (the selected pairs a query, the output and the three
gradients), and the kernels' times at the Keye-VL-2.0 cell's rows.

    chiprun -- python tools/dsa_kernel_sweep.py             # parity + times
    python tools/dsa_kernel_sweep.py --compile              # described v5e

A shape is ``[rows x]tokens:document,document`` (the documents of every
row). Parity runs at 32 / 4 heads of 128 over an indexer of 16 x 64 with
``--parity-top-k`` (so that a short row selects); times at top-k 2,048:
``select`` alone, the forward, and forward + backward, a host clock around
``--iters`` calls, the device finished. One JSON line a case on stdout,
appended to chiprun_out/dsa_kernel_sweep_pr66.jsonl.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

OUT = "chiprun_out/dsa_kernel_sweep_pr66.jsonl"
SHAPES = ["16384:16384", "10752:10240", "2x7552:5062,1682"]
PARITY = ["2048:700,1200", "2x1536:1536"]


def parse(shape):
    grid, docs = shape.split(":")
    rows, _, T = grid.rpartition("x")
    return int(rows or 1), int(T), [int(x) for x in docs.split(",")]


def emit(rec):
    line = json.dumps(rec)
    print(line, flush=True)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as f:
        f.write(line + "\n")


def inputs(rows, T, docs, seed, dtype):
    import jax
    import jax.numpy as jnp
    import numpy as np

    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    shapes = [(32, 128), (4, 128), (4, 128)]
    q, k, v = (jax.random.normal(kk, (rows, T, h, d)).astype(dtype)
               for kk, (h, d) in zip(ks, shapes))
    qi = jax.random.normal(ks[3], (rows, T, 1024)).astype(dtype)
    ki = jax.random.normal(ks[4], (rows, T, 64)).astype(dtype)
    w = jax.random.normal(ks[5], (rows, T, 16)) / 32.0
    seg = np.zeros((rows, T), np.int32)
    at = 0
    for i, n in enumerate(docs):
        seg[:, at:at + n] = i + 1
        at += n
    return q, k, v, qi, ki, w, jnp.asarray(seg)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", nargs="*", default=SHAPES)
    ap.add_argument("--parity", nargs="*", default=PARITY)
    ap.add_argument("--parity-top-k", type=int, default=256)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--compile", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from areal_tpu.models import dsa
    from areal_tpu.models.config import SparseAttnConfig
    from areal_tpu.ops.pallas import sparse_attention as sk

    def entry(sa, impl):
        def f(q, k, v, qi, ki, w, seg):
            def loss(q, k, v):
                o, n = dsa.sparse_attention(q, k, v, qi, ki, w, seg, sa,
                                            impl=impl)
                return jnp.sum(o.astype(jnp.float32) ** 2), (o, n)

            return jax.value_and_grad(loss, argnums=(0, 1, 2),
                                      has_aux=True)(q, k, v)

        return jax.jit(f)

    if args.compile:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        chip = SingleDeviceSharding(topo.devices[0])
        sa = SparseAttnConfig(16, 64, 2048)
        for shape in args.shapes:
            rows, T, docs = parse(shape)
            bf, f32 = jnp.bfloat16, jnp.float32
            like = [jax.ShapeDtypeStruct((rows, T) + tail, dt, sharding=chip)
                    for tail, dt in (((32, 128), bf), ((4, 128), bf),
                                     ((4, 128), bf), ((1024,), bf),
                                     ((64,), bf), ((16,), f32),
                                     ((), jnp.int32))]
            began = time.monotonic()
            got = entry(sa, "pallas").lower(*like).compile()
            emit({"case": "compile", "shape": shape,
                  "seconds": round(time.monotonic() - began, 1),
                  "temp_bytes": got.memory_analysis().temp_size_in_bytes})
        return 0

    sa = SparseAttnConfig(16, 64, args.parity_top_k)
    for shape in args.parity:
        rows, T, docs = parse(shape)
        a = inputs(rows, T, docs, 7, jnp.bfloat16)
        (_, (o_k, n_k)), g_k = entry(sa, "pallas")(*a)
        (_, (o_x, n_x)), g_x = entry(sa, "reference")(*a)
        f32 = jnp.float32

        def rel(x, y):
            return float(jnp.abs(x.astype(f32) - y.astype(f32)).max()
                         / jnp.abs(y.astype(f32)).max())

        emit({"case": "parity", "shape": shape, "top_k": sa.top_k,
              "n_selected_equal": bool((n_k == n_x).all()),
              "n_selected": int(n_k.sum()),
              "host": dsa.host_selected_pairs(docs * rows, sa.top_k),
              "out": rel(o_k, o_x),
              **{f"d{n}": rel(x, y) for n, x, y in zip("qkv", g_k, g_x)}})

    sa = SparseAttnConfig(16, 64, 2048)
    for shape in args.shapes:
        rows, T, docs = parse(shape)
        a = inputs(rows, T, docs, 11, jnp.bfloat16)
        q, k, v, qi, ki, w, seg = a
        more = dsa.padded_len(T) - T
        qi_p, ki_p, w_p, seg_p = (
            jnp.pad(x, [(0, 0), (0, more)] + [(0, 0)] * (x.ndim - 2))
            for x in (qi, ki, w, seg))
        fns = {
            "select": (jax.jit(lambda qi, ki, w, seg: sk.select(
                qi, sk.tiled_key(ki), w, seg, sa.top_k, sa.n_heads)),
                (qi_p, ki_p, w_p, seg_p)),
            "fwd": (jax.jit(lambda *a: dsa.sparse_attention(
                *a, sa, impl="pallas")), a),
            "fwd_bwd": (entry(sa, "pallas"), a),
        }
        rec = {"case": "time", "shape": shape, "top_k": sa.top_k,
               "causal_pairs": dsa.host_causal_pairs(docs * rows),
               "selected_pairs": dsa.host_selected_pairs(docs * rows,
                                                         sa.top_k)}
        for name, (fn, xs) in fns.items():
            jax.block_until_ready(fn(*xs))
            began = time.monotonic()
            for _ in range(args.iters):
                out = fn(*xs)
            jax.block_until_ready(out)
            rec[f"{name}_ms"] = round(
                1e3 * (time.monotonic() - began) / args.iters, 3)
        emit(rec)
    return 0


if __name__ == "__main__":
    sys.exit(main())
