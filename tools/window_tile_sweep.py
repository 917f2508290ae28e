"""Time the grouped-head attention wrapper alone, a tile at a time — the
measurement behind ``ops/pallas/window_attention.TILE_COST`` (windowed),
``CAUSAL_TILE_COST`` (``--window 0``: full causal) and, with ``--window 0
--head-dim 256 --device``, the wide-head table ``WIDE_BLOCKS`` (heads of
256 at the GLM cell's 20 / 20 and the Qwen3-Next cell's 16 / 2 heads; with
``--head-dim 192 --value-dim 128`` the Kimi-Linear cell's 32 / 32).

    python tools/window_tile_sweep.py            # on the chip
    python tools/window_tile_sweep.py --compile  # here, for a described v5e

``--rows`` rows of ``--length`` tokens, ``--window`` (0 = none),
``--heads`` query / key-value heads of ``--head-dim``, bf16. Each layout
of ``--documents`` is the lengths of a row's documents, comma-separated
(``1876,288,511``; what is left of the row is padding; default: the row is
one document): the kernel skips the key blocks a query block's documents
do not reach, so a cell's table is measured at its layout.
``--shapes LENGTH:DOCUMENTS ...`` gives each length its own layout in
place of the product of the two.
Per block shape of ``--blocks`` (``Q``, ``QxKV`` or ``QxKVxCOMPUTE``: the
query block, the key block fetched, the key block computed at a time; or
``FWD/DKV/DQ``, a shape a kernel, the last one ignored under a fused
backward) and per backward of ``--fused`` (0 = dKV and dQ kernels, 1 =
the one fused kernel): the forward alone (what an inference pass and a
recomputation run) and forward + backward (the residual-saving forward
and the backward), through the wrapper — its layout glue included —
host clock around ``block_until_ready`` over ``--iters`` calls; then c =
(2 forward + 1 forward-and-backward) / (rows x query tokens x visited key
tokens at the padded length), in ns — visited by the STATIC mask
(``blocks_visited``); ``needed`` is what the layout leaves of them
(``blocks_needed``). ``--device`` adds DEVICE ms a call from a profiler
capture, the whole call's and each ``splash_mqa_*`` kernel's own
(``fwd_device`` of the forward alone, ``fwd_bwd_device`` of forward +
backward): the three kernels' blocks are separate fields, so each
kernel's best shape is read from its own op. ``--compile`` also prints the
forward + backward program's temporary bytes (``temp_bytes``: the fused
kernel's dQ partial sums live there).
Prints one JSON line per shape and writes them to
``chiprun_out/<--out>.jsonl``.
"""

from __future__ import annotations

import argparse
import functools
import itertools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from areal_tpu.ops.pallas import window_attention as wa  # noqa: E402


def timed(fn, args, iters):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def blocks_of(spec: str, fused: bool) -> "wa.Blocks":
    """``Q``, ``QxKV`` or ``QxKVxCOMPUTE`` for all three kernels, or
    ``FWD/DKV/DQ`` a kernel."""
    def one(part):
        parts = [int(x) for x in part.split("x")]
        bq = parts[0]
        bkv = parts[1] if len(parts) > 1 else bq
        return bq, bkv, parts[2] if len(parts) > 2 else bkv

    kernels = [one(part) for part in spec.split("/")]
    fwd, dkv, dq = (kernels * 3)[:3] if len(kernels) == 1 else kernels
    return wa.Blocks(fwd, dkv, None if fused else dq[:2])


def kernels_ms(ops):
    """{kernel: its own device ms a call} of a capture's ops."""
    out = {}
    for op, ms in ops.items():
        if op.startswith("splash_mqa_"):
            kind = op.split("_")[2]
            out[kind] = out.get(kind, 0.0) + ms
    return {k: round(v, 4) for k, v in out.items()}


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
    ap.add_argument("--shapes", nargs="+", default=None,
                    help="LENGTH:DOCUMENTS pairs, in place of the product "
                         "of --length and --documents")
    ap.add_argument("--device", action="store_true",
                    help="device ms from a profiler capture")
    ap.add_argument("--heads", type=int, nargs=2, default=[32, 4])
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--value-dim", type=int, default=None,
                    help="the value head's width where it is not the "
                    "key's (latent attention's 128 under a key of 192)")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--out", default="window_tile_sweep")
    ap.add_argument("--compile", action="store_true")
    args = ap.parse_args()
    hq, hkv = args.heads
    window = args.window or None
    sharding = None
    if args.compile:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        sharding = SingleDeviceSharding(topo.devices[0])
    R = args.rows
    out_path = f"chiprun_out/{args.out}.jsonl"
    if not args.compile:
        os.makedirs("chiprun_out", exist_ok=True)
        open(out_path, "w").close()
    shapes_of = ([(int(s.split(":")[0]), s.split(":")[1])
                  for s in args.shapes] if args.shapes else
                 [(L, d) for L in args.length for d in args.documents])
    if args.device:
        from ssd_scan_sweep import device_ms
    for L, layout in shapes_of:
        docs = [L] if layout is None else [int(n) for n in layout.split(",")]
        row = np.repeat(np.arange(1, len(docs) + 1), docs)
        row = np.pad(row, (0, L - len(row))).astype(np.int32)
        shapes = [jax.ShapeDtypeStruct((R, L, h, d), jnp.bfloat16,
                                       sharding=sharding)
                  for h, d in ((hq, args.head_dim), (hkv, args.head_dim),
                               (hkv, args.value_dim or args.head_dim))]
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
                    "value_dim": args.value_dim or args.head_dim,
                    "window": args.window, "documents": docs, **what}
            try:
                if args.compile:
                    for f in (fwd, both):
                        done = f.lower(*shapes, seg_shape).compile()
                    line["compiled"] = True
                    line["temp_bytes"] = (
                        done.memory_analysis().temp_size_in_bytes)
                else:
                    t_f = timed(fwd, (q, k, v, seg), args.iters)
                    t_fb = timed(both, (q, k, v, seg), args.iters)
                    step = 2 * t_f + t_fb  # 3 forwards, 1 backward
                    line.update(fwd_ms=t_f * 1e3, fwd_bwd_ms=t_fb * 1e3,
                                step_ms=step * 1e3,
                                c_ns=step * 1e9 / (R * visited_tokens))
                    if args.device:
                        for name, f in (("fwd", fwd), ("fwd_bwd", both)):
                            ms, ops = device_ms(f, (q, k, v, seg),
                                                min(args.iters, 5), top=64)
                            line[f"{name}_device"] = {
                                "ms": round(ms, 4), **kernels_ms(ops)}
            except Exception as e:  # a tile the compiler refuses
                # the head names the kernel, the tail the VMEM asked for
                line["error"] = (f"{type(e).__name__}: {str(e)[:300]} ... "
                                 f"{str(e)[-400:]}")
            print(json.dumps(line), flush=True)
            if not args.compile:
                with open(out_path, "a") as f:
                    f.write(json.dumps(line) + "\n")

        for spec, fused in itertools.product(args.blocks, args.fused):
            blocks = blocks_of(spec, bool(fused))
            # the row is padded to a multiple of every block, and the
            # blocks visited are counted at the forward kernel's
            wa.geometry = lambda *a, blocks=blocks, **kw: blocks
            n_pad = wa.padded_len(L, window)
            bq, bkv, _ = blocks.fwd
            visited, _ = wa.blocks_visited(n_pad, bq, window, bkv)
            needed = int(wa.blocks_needed(
                np.pad(row, (0, n_pad - L)), bq, window, bkv).sum())
            run("grouped",
                functools.partial(wa.window_attention, window=window),
                visited * bq * bkv, blocks=spec, fused_bwd=fused,
                geometry=blocks.label(), padded=n_pad,
                visited=visited, needed=needed)
    return 0


if __name__ == "__main__":
    sys.exit(main())
