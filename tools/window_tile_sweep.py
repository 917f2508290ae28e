"""Time the grouped-head attention wrapper alone, a tile at a time — the
measurement behind ``ops/pallas/window_attention.TILE_COST`` (windowed)
and ``CAUSAL_TILE_COST`` (``--window 0``: full causal).

    python tools/window_tile_sweep.py            # on the chip
    python tools/window_tile_sweep.py --compile  # here, for a described v5e

``--rows`` rows of ``--length`` tokens, ``--window`` (0 = none),
``--heads`` query / key-value heads of ``--head-dim``, bf16. Each layout
of ``--documents`` is the lengths of a row's documents, comma-separated
(``1876,288,511``; what is left of the row is padding; default: the row is
one document): the kernel skips the key blocks a query block's documents
do not reach, so a cell's table is measured at its layout.
Per block shape of ``--blocks`` (``Q``, ``QxKV`` or ``QxKVxCOMPUTE``: the
query block, the key block fetched, the key block computed at a time) and
per backward of ``--fused`` (0 = dKV and dQ kernels, 1 = the one fused
kernel): the forward alone (what an inference pass and a recomputation
run) and forward + backward (the residual-saving forward and the
backward), through the wrapper — its layout glue included — host clock
around ``block_until_ready`` over ``--iters`` calls; then c = (2 forward
+ 1 forward-and-backward) / (rows x query tokens x visited key tokens at
the padded length), in ns — visited by the STATIC mask
(``blocks_visited``); ``needed`` is what the layout leaves of them
(``blocks_needed``).
Prints one JSON line per shape and writes them to
``chiprun_out/<--out>.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental.pallas.ops.tpu.splash_attention import (  # noqa: E402
    splash_attention_kernel as splash,
)

from areal_tpu.ops.pallas import window_attention as wa  # noqa: E402


def timed(fn, args, iters):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def block_sizes(spec: str, fused: bool) -> splash.BlockSizes:
    """``Q``, ``QxKV`` or ``QxKVxCOMPUTE`` for all three kernels."""
    parts = [int(x) for x in spec.split("x")]
    bq = parts[0]
    bkv = parts[1] if len(parts) > 1 else bq
    compute = parts[2] if len(parts) > 2 else bkv
    dq = {} if fused else {"block_q_dq": bq, "block_kv_dq": bkv}
    return splash.BlockSizes(
        block_q=bq, block_kv=bkv, block_kv_compute=compute,
        block_q_dkv=bq, block_kv_dkv=bkv, block_kv_dkv_compute=compute,
        use_fused_bwd_kernel=fused, **dq)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--length", type=int, nargs="+", default=[8192])
    ap.add_argument("--rows", type=int, default=1)
    ap.add_argument("--documents", nargs="+", default=[None],
                    help="layouts: a row's document lengths, "
                         "comma-separated (default: one document)")
    ap.add_argument("--window", type=int, default=1024,
                    help="0: full causal")
    ap.add_argument("--blocks", "--tiles", nargs="+",
                    default=["256", "512", "1024", "2048"])
    ap.add_argument("--fused", type=int, nargs="+", default=[0])
    ap.add_argument("--heads", type=int, nargs=2, default=[32, 4])
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--out", default="window_tile_sweep")
    ap.add_argument("--compile", action="store_true")
    args = ap.parse_args()
    hq, hkv = args.heads
    window = args.window or None
    table = "CAUSAL_TILE_COST" if window is None else "TILE_COST"
    sharding = None
    if args.compile:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        sharding = SingleDeviceSharding(topo.devices[0])
    lines = []
    R = args.rows
    for L, layout in ((L, d) for L in args.length for d in args.documents):
        docs = [L] if layout is None else [int(n) for n in layout.split(",")]
        row = np.repeat(np.arange(1, len(docs) + 1), docs)
        row = np.pad(row, (0, L - len(row))).astype(np.int32)
        shapes = [jax.ShapeDtypeStruct((R, L, h, args.head_dim), jnp.bfloat16,
                                       sharding=sharding)
                  for h in (hq, hkv, hkv)]
        seg_shape = jax.ShapeDtypeStruct((R, L), jnp.int32, sharding=sharding)
        if not args.compile:
            keys = jax.random.split(jax.random.PRNGKey(0), 3)
            q, k, v = (jax.random.normal(kk, s.shape, jnp.float32).astype(
                jnp.bfloat16) for kk, s in zip(keys, shapes))
            seg = jnp.tile(row, (R, 1))

        def run(name, attend, visited_tokens, **what):
            fwd = jax.jit(lambda q, k, v, s: attend(q, k, v, s, s))
            both = jax.jit(jax.grad(
                lambda q, k, v, s: attend(q, k, v, s, s).astype(
                    jnp.float32).sum(), argnums=(0, 1, 2)))
            line = {"kernel": name, "rows": R, "length": L,
                    "heads": [hq, hkv], "head_dim": args.head_dim,
                    "window": args.window, "documents": docs, **what}
            try:
                if args.compile:
                    for f in (fwd, both):
                        f.lower(*shapes, seg_shape).compile()
                    line["compiled"] = True
                else:
                    t_f = timed(fwd, (q, k, v, seg), args.iters)
                    t_fb = timed(both, (q, k, v, seg), args.iters)
                    step = 2 * t_f + t_fb  # 3 forwards, 1 backward
                    line.update(fwd_ms=t_f * 1e3, fwd_bwd_ms=t_fb * 1e3,
                                step_ms=step * 1e3,
                                c_ns=step * 1e9 / (R * visited_tokens))
            except Exception as e:  # a tile the compiler refuses
                line["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            print(json.dumps(line), flush=True)
            lines.append(line)

        for spec in args.blocks:
            for fused in args.fused:
                sizes = block_sizes(spec, bool(fused))
                # the row is padded to a multiple of both blocks, and the
                # blocks visited are counted at the larger
                tile = math.lcm(sizes.block_q, sizes.block_kv)
                setattr(wa, table, {tile: 1.0})
                wa._block_sizes = lambda t, w, d=None, sizes=sizes: sizes
                n_pad = wa.padded_len(L, window)
                visited, _ = wa.blocks_visited(n_pad, tile, window)
                # (a tree from before the kernel skipped blocks has none)
                needed = int(wa.blocks_needed(
                    np.pad(row, (0, n_pad - L)), tile, window).sum()
                ) if hasattr(wa, "blocks_needed") else visited
                run("grouped", lambda q, k, v, s, s2: wa.window_attention(
                    q, k, v, s, s2, window=window), visited * tile * tile,
                    blocks=spec, fused_bwd=fused, padded=n_pad,
                    visited=visited, needed=needed)
    if not args.compile:
        os.makedirs("chiprun_out", exist_ok=True)
        with open(f"chiprun_out/{args.out}.jsonl", "w") as f:
            f.writelines(json.dumps(x) + "\n" for x in lines)
    return 0


if __name__ == "__main__":
    sys.exit(main())
