"""Time the windowed attention kernels alone, a tile at a time — the
measurement behind ``ops/pallas/window_attention.TILE_COST``.

    python tools/window_tile_sweep.py            # on the chip
    python tools/window_tile_sweep.py --compile  # here, for a described v5e

One row of ``--length`` tokens in one document, ``--window``, 32 query / 4
key-value heads of 128, bf16. Per tile: the forward alone (what an
inference pass and a recomputation run) and forward + backward (the
residual-saving forward, dKV, dQ), host clock around
``block_until_ready`` over ``--iters`` calls; then c(t) = (3 forward + 1
backward) / (query tokens x visited key tokens), in ns. The flash kernel
(causal, no window, K/V repeated) is timed at the same shapes beside it.
Prints one JSON line per tile and writes them to
``chiprun_out/window_tile_sweep.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from areal_tpu.ops.pallas import flash_attention as fa  # noqa: E402
from areal_tpu.ops.pallas import window_attention as wa  # noqa: E402


def timed(fn, args, iters):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--length", type=int, nargs="+", default=[8192])
    ap.add_argument("--window", type=int, default=1024)
    ap.add_argument("--tiles", type=int, nargs="+",
                    default=[256, 512, 1024, 2048])
    ap.add_argument("--heads", type=int, nargs=2, default=[32, 4])
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--compile", action="store_true")
    args = ap.parse_args()
    hq, hkv = args.heads
    sharding = None
    if args.compile:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        sharding = SingleDeviceSharding(topo.devices[0])
    lines = []
    for L in args.length:
        shapes = [jax.ShapeDtypeStruct((1, L, h, 128), jnp.bfloat16,
                                       sharding=sharding)
                  for h in (hq, hkv, hkv)]
        seg_shape = jax.ShapeDtypeStruct((1, L), jnp.int32, sharding=sharding)
        if not args.compile:
            keys = jax.random.split(jax.random.PRNGKey(0), 3)
            q, k, v = (jax.random.normal(kk, s.shape, jnp.float32).astype(
                jnp.bfloat16) for kk, s in zip(keys, shapes))
            seg = jnp.ones((1, L), jnp.int32)

        def run(name, attend, tile, visited_tokens):
            fwd = jax.jit(lambda q, k, v, s: attend(q, k, v, s, s))
            both = jax.jit(jax.grad(
                lambda q, k, v, s: attend(q, k, v, s, s).astype(
                    jnp.float32).sum(), argnums=(0, 1, 2)))
            line = {"kernel": name, "length": L, "tile": tile,
                    "window": args.window}
            try:
                if args.compile:
                    for f in (fwd, both):
                        f.lower(*shapes, seg_shape).compile()
                    line["compiled"] = True
                else:
                    t_f = timed(fwd, (q, k, v, seg), args.iters)
                    t_fb = timed(both, (q, k, v, seg), args.iters)
                    step = 2 * t_f + t_fb  # 3 forwards, dKV, dQ
                    line.update(fwd_ms=t_f * 1e3, fwd_bwd_ms=t_fb * 1e3,
                                step_ms=step * 1e3,
                                c_ns=step * 1e9 / visited_tokens)
            except Exception as e:  # a tile the compiler refuses
                line["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            print(json.dumps(line), flush=True)
            lines.append(line)

        for tile in args.tiles:
            if L % tile:
                continue
            wa.TILE_COST = {tile: 1.0}
            visited, _ = wa.blocks_visited(L, tile, args.window)
            run("window", lambda q, k, v, s, s2: wa.window_attention(
                q, k, v, s, s2, window=args.window), tile,
                visited * tile * tile)
        run("flash", fa.flash_attention, fa.pick_tile(L), L * L / 2)
    if not args.compile:
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/window_tile_sweep.jsonl", "w") as f:
            f.writelines(json.dumps(x) + "\n" for x in lines)
    return 0


if __name__ == "__main__":
    sys.exit(main())
