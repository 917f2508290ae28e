"""Time the sorted expert pass alone, a head-room at a time — the
measurement behind ``models/moe._ROW_HEADROOM`` and ``_ROW_TILE``.

    python tools/expert_rows_sweep.py            # on the chip

One micro-batch's expert pass on a shard that holds ``--held`` of
``--routed`` experts (default: Mellum 2's share — 6656 tokens of width
2304, 8 choices a token, 16 of 64 experts of width 896; ``--olmoe`` for
one source's pass of an OLMoE ``e4`` shard: 3968 tokens of 2048, 16 of 64
of width 1024; ``--nemotron`` for Nemotron 3 Super's share: 3712 tokens in
a latent of 1024 padded to whole row tiles, 22 choices, 8 of 512 ungated
``relu2`` experts of 2688), bf16, a uniform random router, so the held
share of the ``M = tokens x choices`` entries is live. Per ``--tokens``:
``moe._sorted_expert_ffn`` as shipped but for the head-room — none (the
whole buffer, no ``cond``), then each of ``--factors`` — forward alone
and forward + backward, host clock around ``block_until_ready`` over
``--iters`` calls. Then the COMBINE alone at the shipped head-room's row
count ``R``, each way (the two costs a rule that chose between them would
compare): ``"rows"`` — ``R`` rows added into their tokens in float32, whose
backward gathers ``R`` rows — and ``"entries"`` — the ``R`` rows padded to
``M``, un-permuted by the inverse permutation (a second sort) and summed
over each token's choices, whose backward scatter-adds ``M`` rows — with
``ns_per_row`` = forward + backward over the rows moved (``R`` / ``M``).
Prints one JSON line per case and writes them to
``chiprun_out/expert_rows_sweep.jsonl`` (``--out`` for another name).

The grouped GEMMs themselves (the measurement behind ``moe.gemm_tiling``):
``--gemm ragged_dot gmm`` times the pass once per way its GEMMs can run —
``jax.lax.ragged_dot``, and the Pallas kernel under the shipped tile rule
and then at each tile of ``--tiling 512x384x896 ...`` (``tm x tD x tF``:
the row tile, the tile along the tokens' width and the tile along the
experts' width, for all three kernels of every GEMM of the pass) — with
``gemm``, ``tiling`` and ``roofline_pct`` (the benchmark's cost of the
live rows at the chip's peaks over the time) beside ``fwd_ms`` /
``fwd_bwd_ms``. Default: as shipped on this backend.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from areal_tpu.models import moe  # noqa: E402
from areal_tpu.ops import attention  # noqa: E402
from benchmark import moe_cost, peaks, ssm_cost  # noqa: E402


def timed(fn, args, iters):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, nargs="+", default=None)
    ap.add_argument("--factors", type=float, nargs="+",
                    default=[1.25, 1.5, 2.0, 3.0])
    ap.add_argument("--olmoe", action="store_true")
    ap.add_argument("--nemotron", action="store_true")
    ap.add_argument("--out", default="expert_rows_sweep.jsonl")
    ap.add_argument("--held", type=int, default=None)
    ap.add_argument("--routed", type=int, default=None)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--gemm", nargs="+", default=["shipped"],
                    choices=["shipped", "ragged_dot", "gmm"])
    ap.add_argument("--tiling", nargs="+", default=[],
                    help="tm x tD x tF, e.g. 512x384x896")
    args = ap.parse_args()
    # width, experts' width, choices, held, routed, tokens, gated, act
    D, F, k, G, E, N0, gated, act = (
        (1024, 2688, 22, 8, 512, 3712, False, moe.relu2) if args.nemotron
        else (2048, 1024, 8, 16, 64, 3968, True, jax.nn.silu) if args.olmoe
        else (2304, 896, 8, 16, 64, 6656, True, jax.nn.silu))
    tokens = args.tokens or [N0]
    G, E = args.held or G, args.routed or E
    # How the pass's GEMMs run: (moe_mlp's impl, tile) — as shipped on this
    # backend, ragged_dot, the kernel under the shipped rule, the kernel at
    # one tile.
    ways = [way for gemm in args.gemm for way in {
        "shipped": [("auto", None)], "ragged_dot": [("reference", None)],
        "gmm": [("pallas", None)] + [
            ("pallas", tuple(int(x) for x in t.split("x")))
            for t in args.tiling]}[gemm]]
    cost = moe_cost.grouped_ffn_cost if gated else ssm_cost.latent_ffn_cost
    out_path = os.path.join("chiprun_out", args.out)
    os.makedirs("chiprun_out", exist_ok=True)
    dev = jax.devices()[0]
    lines = []
    for N in tokens:
        M = N * k
        keys = jax.random.split(jax.random.PRNGKey(N), 6)
        xf = jax.random.normal(keys[0], (N, D), jnp.bfloat16)
        if args.nemotron:  # a latent source is whole row tiles
            xf = moe._whole_row_tiles(xf)
        top_i = jax.random.randint(keys[1], (N, k), 0, E)
        gates = jax.random.uniform(keys[2], (M,), jnp.float32)
        w = [jax.random.normal(kk, s, jnp.bfloat16) * 0.02 for kk, s in zip(
            keys[3:], ((G, D, F), (G, D, F), (G, F, D)))]
        if not gated:
            w[0] = None
        eid = moe._held_eid(top_i, jnp.ones((N,)), 0, G)
        live = int(jnp.sum(eid < G))
        case = {"tokens": N, "entries": M, "live": live, "held": G,
                "routed": E, "D": D, "F": F, "device": dev.device_kind,
                "platform": dev.platform}

        a = (xf, gates, *[m for m in w if m is not None])
        shipped = moe._ROW_HEADROOM, getattr(moe, "gemm_tiling", None)
        try:
            for (impl, tile), factor in itertools.product(
                    ways, [None] + sorted(args.factors)):
                if impl == "pallas" and factor is None:
                    continue  # the whole buffer never runs the kernel
                if tile is not None:  # (rows, k, n, groups) -> tm, tk, tn
                    moe.gemm_tiling = lambda rows, k, n, groups, t=tile: (
                        t[0], *((t[1], t[2]) if (k, n) == (D, F)
                                else (t[2], t[1])))
                elif shipped[1] is not None:
                    moe.gemm_tiling = shipped[1]
                jax.clear_caches()  # the pass is jitted: by shape, not tile
                gemm = ("ragged_dot" if not attention._wants_kernel(impl)
                        else "gmm")
                moe._ROW_HEADROOM = E / G if factor is None else factor
                rows = moe.sorted_rows(M, G, E)
                if rows < live:
                    continue  # would time the fallback

                # jit caches by function: new functions per case.
                def ffn(xf, gates, *w, rows=rows, gemm=gemm):
                    if not gated:
                        w = (None,) + w
                    return moe._sorted_expert_ffn(
                        xf, eid, gates, None, *w, rows, act, k,
                        **({"gemm": gemm} if shipped[1] else {}))[0]

                def loss(*a):
                    return jnp.sum(ffn(*a).astype(jnp.float32) ** 2)

                line = dict(case, headroom=factor, rows=rows, gemm=gemm,
                            tiling=tile and "%dx%dx%d" % tile)
                try:
                    fwd = timed(jax.jit(ffn), a, args.iters)
                    both = timed(jax.jit(jax.value_and_grad(
                        loss, argnums=tuple(range(len(a))))), a, args.iters)
                except Exception as e:  # a tile the compiler refuses
                    line["error"] = str(e).strip().splitlines()[-1][:200]
                else:
                    # forward + backward: the algorithm's least time for
                    # the LIVE rows at the chip's peaks, over the time
                    least = sum(peaks.least_time(
                        *cost(live, 1, G, D, F, bwd), dev.device_kind)[0]
                        for bwd in (False, True)
                    ) if dev.device_kind in peaks.PEAKS else None
                    line.update(
                        fwd_ms=round(fwd * 1e3, 4),
                        fwd_bwd_ms=round(both * 1e3, 4),
                        roofline_pct=least and round(100 * least / both, 2))
                    if shipped[1] is not None:
                        line["gemms"] = {
                            "%dx%dx%d/%d" % key: how if how != "gmm"
                            else "gmm:%dx%dx%d" % moe.gemm_tiling(*key)
                            for key, how in moe.gemm_counts().items()}
                        moe._GEMMS.clear()
                print(json.dumps(line), flush=True)
                lines.append(line)
        finally:
            moe._ROW_HEADROOM = shipped[0]
            if shipped[1] is not None:
                moe.gemm_tiling = shipped[1]

        # The combine alone, each way, at the shipped bound's row count.
        R = moe.sorted_rows(M, G, E)
        order = jnp.argsort(eid)
        ys = jax.random.normal(keys[0], (R, D), jnp.bfloat16)
        T = xf.shape[0]

        def add_rows(ys):
            return jnp.zeros((T, D), jnp.float32).at[order[:R] // k].add(
                ys.astype(jnp.float32)).astype(ys.dtype)

        def unpermute_entries(ys):
            ys = jnp.pad(ys, ((0, M - R), (0, 0)))
            return jnp.sum(jnp.take(ys, jnp.argsort(order), axis=0).reshape(
                N, k, D), axis=1)

        for combine, fn, moved in (("rows", add_rows, R),
                                   ("entries", unpermute_entries, M)):
            fwd = timed(jax.jit(fn), (ys,), args.iters)
            both = timed(jax.jit(jax.value_and_grad(
                lambda ys, fn=fn: jnp.sum(fn(ys).astype(jnp.float32) ** 2))),
                (ys,), args.iters)
            line = dict(case, combine=combine, rows=R, token_rows=T,
                        rows_moved=moved, fwd_ms=round(fwd * 1e3, 4),
                        fwd_bwd_ms=round(both * 1e3, 4),
                        ns_per_row=round(both * 1e9 / moved, 2))
            print(json.dumps(line), flush=True)
            lines.append(line)
    with open(out_path, "w") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
