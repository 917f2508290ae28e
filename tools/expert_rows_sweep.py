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

Then the GLUE alone (the measurement behind ``moe._WALK_ROWS``; PERF.md §5,
PR 51): the two row movements of a bounded pass at ``R`` rows and their
transposes — the row gather, its VJP (a scatter-add of ``R`` bfloat16
rows), the combine's float32 scatter-add, its VJP (a gather) — DEVICE
milliseconds a call from a profiler capture (the union of the device's
``XLA Ops`` events), three ways: ``"ops"`` — ``jnp.take`` / ``.at[].add``
over all ``R`` rows, dead ones at their real tokens (the pass before PR
51); ``"oob"`` — the same ops with the dead rows' indices sent past the
tokens under ``mode="fill"`` / ``"drop"``; ``"walk"`` — both as loops over
the live head (``moe._add_live``, and a gather written the same way here),
at each of ``--walk`` rows a step; ``"shipped"`` — ``moe._gather_live`` /
``_add_live`` as they ship (one gather of ``R`` rows, the adds as loops).
``ns_per_row`` is the four over ``R``, ``ns_per_live_row`` over the live
rows. ``--live`` sets the live share of the ``M`` entries (default:
what the uniform router gives, the held share); ``--trinity`` is
Trinity-Mini's share (11776 tokens of 2048, 8 choices, 8 of 128 experts of
1024); ``--parts`` picks among ``pass``, ``combine`` and ``glue``;
``--device`` adds to a pass's line its two times from such a capture
(``fwd_device_ms``, ``fwd_bwd_device_ms``) and its eight largest ops.

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


def device_ms(fn, args, iters, top=8):
    """(device milliseconds a call of ``fn(*args)``, {op: ms a call} of the
    ``top`` largest by name) from a profiler capture of ``iters`` calls:
    the union of the first device's ``XLA Ops`` events (a loop's event
    spans its body's, so a sum would count those twice). (None, {}) where
    the capture holds no such line (no TPU: a rehearsal)."""
    import glob
    import tempfile

    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for _ in range(iters):
                out = fn(*args)
            jax.block_until_ready(out)
        path = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                      "*.xplane.pb"))[0]
        data = jax.profiler.ProfileData.from_file(path)
    events = [
        (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
        for plane in data.planes if plane.name.startswith("/device:TPU:0")
        for line in plane.lines if line.name == "XLA Ops"
        for ev in line.events]
    busy, end, ops = 0.0, 0.0, {}
    for a, b, name in sorted(events):
        busy += max(b, end) - max(a, end)
        end = max(b, end)
        name = name.split(" = ")[0].lstrip("%").rstrip(".0123456789")
        ops[name] = ops.get(name, 0.0) + (b - a) / 1e6 / iters
    return (busy / 1e6 / iters if events else None,
            {k: round(v, 4) for k, v in
             sorted(ops.items(), key=lambda kv: -kv[1])[:top]})


def glue_lines(case, xf, tok, live, R, walks, iters):
    """The two row movements of a bounded pass and their transposes, each
    way (the module's docstring), device ms a call."""
    T, D = xf.shape
    ys = jax.random.normal(jax.random.PRNGKey(R), (R, D), xf.dtype)
    ct = jax.random.normal(jax.random.PRNGKey(T), (T, D), xf.dtype)
    n_live = jnp.asarray(live, jnp.int32)  # traced, as a pass's count is

    def adder(at, **kw):
        return lambda ys: jnp.zeros((T, D), jnp.float32).at[at].add(
            ys.astype(jnp.float32), **kw).astype(ys.dtype)

    def ops(n):
        return lambda xf: jnp.take(xf, tok, axis=0), adder(tok)

    def oob(n):
        out = jnp.where(jnp.arange(R) < n, tok, T)  # dead rows: no token
        return (lambda xf: jnp.take(xf, out, axis=0, mode="fill",
                                    fill_value=0), adder(out, mode="drop"))

    def loop_gather(xf, n):  # the gather as a loop too: what PR 51 tried
        step, steps = moe._walk(R, n)

        def one(i, xs):
            start = jnp.minimum(i * step, R - step)
            at = jax.lax.dynamic_slice(tok, (start,), (step,))
            at = jnp.where(start + jnp.arange(step) < n, at, T)
            return jax.lax.dynamic_update_slice(
                xs, jnp.take(xf, at, axis=0, mode="fill", fill_value=0),
                (start, 0))

        return jax.lax.fori_loop(0, steps, one, jnp.zeros((R, D), xf.dtype))

    def walk(n):  # each loop the other's transpose, by hand
        gather = jax.custom_vjp(lambda xf: loop_gather(xf, n))
        gather.defvjp(lambda xf: (gather(xf), None),
                      lambda _, c: (moe._add_live(T, c, tok, n),))
        add = jax.custom_vjp(lambda ys: moe._add_live(T, ys, tok, n))
        add.defvjp(lambda ys: (add(ys), None),
                   lambda _, c: (loop_gather(c, n),))
        return gather, add

    def shipped(n):
        return (lambda xf: moe._gather_live(T, xf, tok, n),
                lambda ys: moe._add_live(T, ys, tok, n))

    def four(make):  # both movements and both VJPs: (name, fn, argument)
        def vjp(which, like):
            return lambda c, n: jax.vjp(
                make(n)[which], jnp.zeros_like(like))[1](c)[0]

        return (("gather", lambda x, n: make(n)[0](x), xf),
                ("add", lambda y, n: make(n)[1](y), ys),
                ("gather_vjp", vjp(0, xf), ys), ("add_vjp", vjp(1, ys), ct))

    ways = [("ops", None, ops), ("oob", None, oob)]
    if hasattr(moe, "_gather_live"):
        ways += [("walk", rows, walk) for rows in walks]
        ways += [("shipped", None, shipped)]
    walk_rows = getattr(moe, "_WALK_ROWS", None)
    try:
        for way, rows, make in ways:
            if walk_rows is not None:
                moe._WALK_ROWS = rows or walk_rows
            line = dict(case, glue=way, rows=R, token_rows=T, walk_rows=rows)
            try:
                ms = {name: device_ms(jax.jit(fn), (arg, n_live), iters)[0]
                      for name, fn, arg in four(make)}
            except Exception as e:  # a shape the compiler refuses
                line["error"] = str(e).strip().splitlines()[-1][:200]
            else:
                if None not in ms.values():
                    total = sum(ms.values())
                    line.update(
                        {f"{name}_ms": round(v, 4) for name, v in ms.items()},
                        ms=round(total, 4),
                        ns_per_row=round(total * 1e6 / R, 2),
                        ns_per_live_row=round(total * 1e6 / max(live, 1), 2))
            yield line
    finally:
        if walk_rows is not None:
            moe._WALK_ROWS = walk_rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, nargs="+", default=None)
    ap.add_argument("--factors", type=float, nargs="+",
                    default=[1.25, 1.5, 2.0, 3.0])
    ap.add_argument("--olmoe", action="store_true")
    ap.add_argument("--nemotron", action="store_true")
    ap.add_argument("--trinity", action="store_true")
    ap.add_argument("--live", type=float, default=None,
                    help="live share of the entries (default: the held)")
    ap.add_argument("--walk", type=int, nargs="+", default=[512, 1024, 2048],
                    help="rows a step of the live walk")
    ap.add_argument("--device", action="store_true",
                    help="the pass's device ms and largest ops as well")
    ap.add_argument("--parts", nargs="+", default=["pass", "combine", "glue"],
                    choices=["pass", "combine", "glue"])
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
        else (2048, 1024, 8, 8, 128, 11776, True, jax.nn.silu) if args.trinity
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
        if args.live is not None:  # that share of the entries, at random
            here = jax.random.permutation(keys[1], M) < round(args.live * M)
            eid = jnp.where(here, jax.random.randint(keys[2], (M,), 0, G), G)
        live = int(jnp.sum(eid < G))
        case = {"tokens": N, "entries": M, "live": live, "held": G,
                "routed": E, "D": D, "F": F, "device": dev.device_kind,
                "platform": dev.platform}

        a = (xf, gates, *[m for m in w if m is not None])
        shipped = moe._ROW_HEADROOM, getattr(moe, "gemm_tiling", None)
        try:
            for (impl, tile), factor in itertools.product(
                    ways if "pass" in args.parts else [],
                    [None] + sorted(args.factors)):
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
                    grad = jax.jit(jax.value_and_grad(
                        loss, argnums=tuple(range(len(a)))))
                    both = timed(grad, a, args.iters)
                    if args.device:  # the same two from the device's line
                        for name, fn in (("fwd", jax.jit(ffn)),
                                         ("fwd_bwd", grad)):
                            ms, ops = device_ms(fn, a, args.iters)
                            line[f"{name}_device_ms"] = ms and round(ms, 4)
                            line[f"{name}_ops"] = ops
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

        for combine, fn, moved in ((("rows", add_rows, R),
                                    ("entries", unpermute_entries, M))
                                   if "combine" in args.parts else ()):
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
        if "glue" in args.parts:
            for line in glue_lines(case, xf, order[:R] // k, live, R,
                                   args.walk, args.iters):
                print(json.dumps(line), flush=True)
                lines.append(line)
    with open(out_path, "w") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
