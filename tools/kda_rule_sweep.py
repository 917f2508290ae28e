#!/usr/bin/env python3
"""The delta rule with a decay a key channel (ops/pallas/kda_rule.py) on
the chip: the compiled kernel pair against the recurrence a token at a time
(and, with ``--xla``, the XLA form of the same chunk algebra,
models/kda._rule_xla), forward / forward + backward times at the
Kimi-Linear cell's grids, the chunks a grid step of either kernel, and
what the parts of a step cost.

    chiprun -- python tools/kda_rule_sweep.py            # parity + times
    chiprun -- python tools/kda_rule_sweep.py --ablate   # a step's parts
    chiprun -- python tools/kda_rule_sweep.py --steps 4 8 16
    chiprun -- python tools/kda_rule_sweep.py --parts ends [--parity]
    python tools/kda_rule_sweep.py --compile             # described v5e, here

``--parts ends`` times the MIXER between its convolution and its
out-projection (models/kda.py), all 32 heads, from the arrays as the mixer
has them — the convolution's output, the two gates' pre-activations, β's
logits — both ways: ``xla-ends`` is the mixer's XLA text around the plain
kernels, a group of 8 heads at a time under ``lax.map(checkpoint(..))``
(what ran before PR 65), ``in-kernel`` the fused entry
(``kda.rule_with_ends``; left out on a tree that has none, so the tool runs
on the parent too). DEVICE milliseconds a call from a profiler capture,
forward and forward + backward, with the two kernels' own and the largest
ops; ``--parity`` instead gives each form's distance in bfloat16 from the
XLA text in float32 (max |a − b| over max |b|, y and every gradient);
``--compile`` its temporaries for a described v5e.

A shape is ``[rows x]tokens:document,document`` (the documents of every
row). One JSON line a case on stdout (appended to
chiprun_out/kda_rule_sweep_pr65.jsonl), each with the module's
``step_counts()``. Times are a host clock around ``--iters`` calls in a
row, the device finished (a call is milliseconds: the ~0.7 ms round trip
is in the noise of ten); heads run a group of 8 at a time, as the mixer
runs them. ``--ablate`` is the TOOL's: it replaces one part of the module
at a time by a stand-in of no work (the results are then wrong, the time
is what is read) — the decay blocks (or their three finest levels), the
inverse, the states' chain, all three.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

OUT = "chiprun_out/kda_rule_sweep_pr65.jsonl"
# The cell's grids (6 x 1 x 10,752 and 4 x 2 x 7,552 a step) beside the
# 8,192 tokens PR 63 read.
SHAPES = ["8192:1658,6480", "10752:3321,4403,3011", "2x7552:5062,1682"]


def parse(shape):
    grid, docs = shape.split(":")
    rows, _, T = grid.rpartition("x")
    return int(rows or 1), int(T), [int(x) for x in docs.split(",")]


def inputs(jnp, jax, R, T, H, D, docs, dtype, strong, seed=0):
    from areal_tpu.models import gdn

    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = (gdn.l2_normalize(jax.random.normal(ks[0], (R, T, H, D)))
         * D ** -0.5).astype(dtype)
    k = gdn.l2_normalize(jax.random.normal(ks[1], (R, T, H, D))).astype(dtype)
    v = jax.random.normal(ks[2], (R, T, H, D)).astype(dtype)
    g = -jax.nn.softplus(jax.random.normal(ks[3], (R, T, H, D)) - 3) * strong
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (R, T, H)))
    return q, k, v, g, beta, segments(jnp, R, T, docs)


def segments(jnp, R, T, docs):
    """Segment ids [R, T]: every row the documents ``docs``, padding behind."""
    starts = [0]
    for n in docs:
        starts.append(starts[-1] + n)
    pos = jnp.arange(T)
    seg = sum((pos >= s).astype(jnp.int32) for s in starts[:-1])
    return jnp.broadcast_to(jnp.where(pos < starts[-1], seg, 0), (R, T))


def token_scan(jax, jnp, q, k, v, g, beta, seg):
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    R, _, H, dk = q.shape

    def step(carry, x):
        S, prev = carry
        q, k, v, g, b, s = x
        S = jnp.where((s != prev)[:, None, None, None], 0.0, S)
        S = jnp.exp(g)[..., None] * S
        d = b[..., None] * (v - jnp.einsum("rhkv,rhk->rhv", S, k))
        S = S + k[..., None] * d[:, :, None, :]
        return (S, s), jnp.einsum("rhkv,rhk->rhv", S, q)

    _, o = jax.lax.scan(
        step, (jnp.zeros((R, H, dk, dk)), jnp.full((R,), -1, jnp.int32)),
        tuple(jnp.swapaxes(a, 0, 1) for a in (q, k, v, g, beta, seg)))
    return jnp.swapaxes(o, 0, 1)


ENDS_NAMES = ("y", "dx", "da", "dgate", "db", "dA_log", "ddt_bias", "dnorm")
EPS = 1e-5  # the configuration's rms_norm_eps


def ends_inputs(jax, jnp, R, T, H, D, docs, seed=0):
    """The mixer's arrays behind its convolution and its gates' matmuls,
    bfloat16, [q | k | v] as the parent's projection lays them out."""
    bf = jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    x = (2.0 * jax.random.normal(ks[0], (R, T, 3 * H * D))).astype(bf)
    a = (jax.random.normal(ks[1], (R, T, H * D)) - 2.0).astype(bf)
    gate = (2.0 * jax.random.normal(ks[2], (R, T, H * D))).astype(bf)
    b = jax.random.normal(ks[3], (R, T, H)).astype(bf)
    A_log = jnp.log(jax.random.uniform(ks[4], (H,), minval=1.0, maxval=16.0))
    dt_bias = jax.random.normal(ks[5], (H * D,)) - 3.0
    norm = 1.0 + 0.3 * jax.random.normal(ks[6], (D,))
    wt = jax.random.normal(ks[7], (R, T, H * D))
    return x, a, gate, b, A_log, dt_bias, norm, wt


def ends_fn(jax, jnp, kda, how, impl, seg, H, D, dtype, grads=True):
    """The mixer between convolution and out-projection, jitted: y, or
    ((Σ wt · y, y), its gradients)."""
    from areal_tpu.models.gdn import _HEAD_GROUPS, l2_normalize

    f32 = jnp.float32

    def xla_ends(x, a, gate, b, A_log, dt_bias, norm):
        """``kda_mixer``'s text before PR 65, from the convolution's
        output on: a group of heads at a time under a checkpoint."""
        R, T, _ = x.shape
        n = _HEAD_GROUPS
        Hn = H // n

        def by_group(v, parts=1):
            w = v.shape[-1] // (parts * n)
            v = v.reshape(v.shape[:-1] + (parts, n, w))
            return jnp.moveaxis(v, -2, 0).reshape((n,) + v.shape[:-3]
                                                  + (parts * w,))

        def heads(xs):
            x, a, gate, b, A_log, dt_bias = xs
            q, k, v = (t.reshape(R, T, Hn, D) for t in jnp.split(
                jax.nn.silu(x), 3, axis=-1))
            beta = jax.nn.sigmoid(b.astype(f32))
            g = -jnp.exp(A_log.astype(f32))[:, None] * jax.nn.softplus(
                a.astype(f32).reshape(R, T, Hn, D)
                + dt_bias.astype(f32).reshape(Hn, D))
            q = (l2_normalize(q) * D ** -0.5).astype(dtype)
            k = l2_normalize(k).astype(dtype)
            o = kda.channel_decay_rule(q, k, v, g, beta, seg, 64, impl)
            o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                                  + EPS)
            y = (o * norm.astype(f32)).astype(dtype)
            return (y.reshape(R, T, Hn * D).astype(f32)
                    * jax.nn.sigmoid(gate.astype(f32))).astype(dtype)

        y = jax.lax.map(jax.checkpoint(heads), (
            by_group(x, 3), by_group(a), by_group(gate), by_group(b),
            by_group(A_log), by_group(dt_bias)))
        return jnp.moveaxis(y, 0, 2).reshape(R, T, H * D)

    def loss(x, a, gate, b, A_log, dt_bias, norm, wt):
        x, a, gate, b = (t.astype(dtype) for t in (x, a, gate, b))
        if how == "in-kernel":
            y = kda.rule_with_ends(
                x, a, gate, jax.nn.sigmoid(b.astype(f32)), A_log, dt_bias,
                norm, seg, 64, EPS, impl)
        else:
            y = xla_ends(x, a, gate, b, A_log, dt_bias, norm)
        return jnp.sum(y.astype(f32) * wt), y

    if not grads:
        return jax.jit(lambda *xs: loss(*xs)[1])
    # y comes back beside the gradients: a train step needs the forward's
    # result, and without it XLA drops the grouped form's first forward
    return jax.jit(jax.value_and_grad(loss, argnums=tuple(range(7)),
                                      has_aux=True))


def heads_together(jnp, x, H, back=False):
    """[.., q | k | v] <-> a head's [q | k | v] side by side (the fused
    entry's layout), on the tool's random activations."""
    shape = (H, 3) if back else (3, H)
    by = x.reshape(x.shape[:-1] + shape + (x.shape[-1] // (3 * H),))
    return jnp.swapaxes(by, -3, -2).reshape(x.shape)


def ends(args, jax, jnp, kda, rule, emit, chip):
    """``--parts ends`` at every shape."""
    import numpy as np

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__))))
    from ssd_scan_sweep import device_ms

    H, D = 32, 128
    impl = ("pallas_interpret" if args.interpret else "pallas")
    hows = ["xla-ends"] + (
        ["in-kernel"] if hasattr(kda, "rule_with_ends") else [])
    bf, f32 = jnp.bfloat16, jnp.float32

    def placed(how, xs):  # the fused entry takes a head's [q | k | v]
        return ((heads_together(jnp, xs[0], H),) + tuple(xs[1:])
                if how == "in-kernel" else xs)

    def kernel_ms(ops):
        return {f"{name}_ms": sum(ms for op, ms in ops.items()
                                  if op.split(".")[0] == name)
                for name in (rule.FWD_NAME, rule.BWD_NAME)}

    for shape in args.shapes:
        R, T, docs = parse(shape)
        xs = ends_inputs(jax, jnp, R, T, H, D, docs)
        seg = segments(jnp, R, T, docs)
        if args.parity:
            def y_and_grads(how, impl, dtype):
                (_, y), gs = ends_fn(jax, jnp, kda, how, impl, seg, H, D,
                                     dtype)(*placed(how, xs))
                gs = list(gs)
                if how == "in-kernel":
                    gs[0] = heads_together(jnp, gs[0], H, back=True)
                return [np.asarray(v, np.float32) for v in (y, *gs)]

            with jax.default_matmul_precision("highest"):
                exact = y_and_grads("xla-ends", "xla", f32)
            got = {how: y_and_grads(how, impl, bf) for how in hows}
            emit({"parts": "ends", "parity": shape, "impl": impl,
                  "finite": {how: all(bool(np.isfinite(v).all()) for v in vs)
                             for how, vs in got.items()},
                  **{f"{how}_vs_float32": dict(zip(ENDS_NAMES, (
                      float(np.max(np.abs(v - e)) / np.max(np.abs(e)))
                      for v, e in zip(vs, exact))))
                     for how, vs in got.items()},
                  **{f"{how}_y_median_rel_err": float(
                      np.median(np.abs(vs[0] - exact[0]))
                      / np.median(np.abs(exact[0])))
                     for how, vs in got.items()}})
            continue
        for how in hows:
            line = {"parts": "ends", "shape": shape, "impl": how,
                    "heads": H, "dtype": "bfloat16"}
            placed_xs = placed(how, xs)
            if args.compile:
                sds = [jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=chip)
                       for v in placed_xs]
                t0 = time.time()
                c = ends_fn(jax, jnp, kda, how, "pallas", seg, H, D, bf
                            ).lower(*sds).compile()
                emit({**line, "seconds": round(time.time() - t0, 2),
                      "kernels": c.as_text().count("tpu_custom_call"),
                      "temp_mb": c.memory_analysis().temp_size_in_bytes
                      / 1e6})
                continue
            try:
                f, f_ops = device_ms(ends_fn(
                    jax, jnp, kda, how, impl, seg, H, D, bf, grads=False),
                    placed_xs, args.iters)
                fb, fb_ops = device_ms(ends_fn(
                    jax, jnp, kda, how, impl, seg, H, D, bf), placed_xs,
                    args.iters)
            except Exception as e:  # noqa: BLE001 — the record is the point
                emit({**line, "error": repr(e)[-400:]})
                continue
            emit({**line, "fwd_ms": f, "fwd_bwd_ms": fb, **kernel_ms(fb_ops),
                  "fwd_kernel_ms": kernel_ms(f_ops)[rule.FWD_NAME + "_ms"],
                  "fwd_ops": f_ops, "fwd_bwd_ops": fb_ops})


def ablations(jnp, rule):
    """{name: the module's functions that a stand-in of no work replaces}."""
    halvings = rule._halvings

    def blocks(c, qf, kf, kbf, cd, exact):
        z = jnp.zeros(c.shape[:2] + c.shape[1:2], jnp.float32)
        return z, z

    def product(spec, a, b, exact):
        ins, out = spec.split("->")
        dims = {x: d for s, t in zip(ins.split(","), (a, b))
                for x, d in zip(s, t.shape)}
        return (jnp.zeros([dims[x] for x in out], jnp.float32)
                + jnp.sum(a.astype(jnp.float32)) * 0
                + jnp.sum(b.astype(jnp.float32)) * 0)

    def column(kappa, width):
        return jnp.zeros(kappa.shape[:1] + (kappa.shape[2], width),
                         jnp.float32) + jnp.max(kappa)

    def inverses(A, exact):
        return A

    def walk(nc, chunk):
        return None

    parts = {"decay_blocks": ("_decay_blocks", blocks),
             "inverse": ("_unit_inverses", inverses),
             "chain": ("_walk", walk)}
    cases = {"whole": []}
    cases.update({"no_" + n: [p] for n, p in parts.items()})
    # the decay blocks' levels w = 8, 16, 32 alone: three products of six
    cases["levels_from_8"] = [("_halvings", lambda c: halvings(c)[3:])]
    cases["none_of_the_three"] = list(parts.values())
    # ... and of what then remains: the cumulated decay (a float32 product),
    # κ down the sublanes (a transpose), the six products of U, W, G, C, P W
    # and P U
    rest = {"cumulate": ("_cumulate", lambda g: g),
            "column": ("_column", column), "products": ("_product", product)}
    for n, p in rest.items():
        cases["none_nor_" + n] = list(parts.values()) + [p]
    cases["none_nor_any"] = list(parts.values()) + list(rest.values())
    return cases


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--compile", action="store_true")
    ap.add_argument("--ablate", action="store_true")
    ap.add_argument("--xla", action="store_true")
    ap.add_argument("--interpret", action="store_true",
                    help="rehearse on the CPU, at a tiny shape")
    ap.add_argument("--parts", choices=("rule", "ends"), default="rule")
    ap.add_argument("--parity", action="store_true",
                    help="with --parts ends: distances, no times")
    ap.add_argument("--steps", type=int, nargs="*", default=[])
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--shapes", nargs="*", default=SHAPES)
    ap.add_argument("--dtypes", nargs="*", default=["bfloat16", "float32"])
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp

    from areal_tpu.models import kda
    from areal_tpu.ops.pallas import kda_rule as rule

    chip = None
    if args.compile:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        jax.config.update("jax_enable_compilation_cache", False)
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        chip = SingleDeviceSharding(topo.devices[0])
    H, D = args.heads, 128
    os.makedirs("chiprun_out", exist_ok=True)

    def emit(line):
        line["step_counts"] = [list(k) + [n]
                               for k, n in rule.step_counts().items()]
        line["device"] = ("described v5e" if args.compile
                          else jax.devices()[0].device_kind)
        head_chunks = line.pop("head_chunks", None)
        if "kernel_fwd_ms" in line:
            line["fwd_us_head_chunk"] = round(
                1e3 * line["kernel_fwd_ms"] / head_chunks, 3)
        print(json.dumps(line), flush=True)
        if not args.interpret:  # a rehearsal's times are no one's numbers
            with open(OUT, "a") as f:
                f.write(json.dumps(line) + "\n")

    def loss(fn):
        return lambda q, k, v, g, b, seg: jnp.sum(
            jnp.sin(fn(q, k, v, g, b, seg).astype(jnp.float32)))

    def kernel(q, k, v, g, b, seg):
        return kda.channel_decay_rule(
            q, k, v, g, b, seg, 64,
            "pallas_interpret" if args.interpret else "pallas")

    def xla(q, k, v, g, b, seg):
        return kda.channel_decay_rule(q, k, v, g, b, seg, 64, "xla")

    def grad_of(fn):
        return jax.jit(jax.grad(loss(fn), argnums=(0, 1, 2, 3, 4)))

    def timed(f, a):
        jax.block_until_ready(f(*a))
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = f(*a)
        jax.block_until_ready(out)
        return round(1e3 * (time.perf_counter() - t0) / args.iters, 3)

    def times(a, line, between=lambda: None):
        """A fresh trace of the kernel pair (the module as it stands) into
        ``line``, or what the chip's compiler refused of it."""
        try:
            line["kernel_fwd_ms"] = timed(jax.jit(lambda *x: kernel(*x)), a)
            between()
            line["kernel_grad_ms"] = timed(grad_of(lambda *x: kernel(*x)), a)
        except Exception as e:  # noqa: BLE001 — the record is the point
            line["error"] = repr(e)[:300]

    if args.parts == "ends":
        ends(args, jax, jnp, kda, rule, emit, chip)
        return 0
    for shape in args.shapes:
        R, T, docs = parse(shape)
        chunks = R * H * -(-T // 64)
        if args.compile:
            for dtype in (jnp.bfloat16, jnp.float32):
                sds = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in (
                    ((R, T, H, D), dtype), ((R, T, H, D), dtype),
                    ((R, T, H, D), dtype), ((R, T, H, D), jnp.float32),
                    ((R, T, H), jnp.float32), ((R, T), jnp.int32))]
                t0 = time.time()
                c = jax.jit(jax.value_and_grad(
                    loss(kernel), argnums=(0, 1, 2, 3, 4))).lower(
                        *sds).compile()
                emit({"compile": shape, "dtype": jnp.dtype(dtype).name,
                      "seconds": round(time.time() - t0, 2),
                      "temp_bytes": c.memory_analysis().temp_size_in_bytes,
                      "kernels": [n for n in (rule.FWD_NAME, rule.BWD_NAME)
                                  if n in c.as_text()]})
            continue
        if args.ablate or args.steps:
            a = inputs(jnp, jax, R, T, H, D, docs, jnp.bfloat16, 1.0)
            for name, parts in (ablations(jnp, rule).items()
                                if args.ablate else ()):
                kept = [(n, getattr(rule, n)) for n, _ in parts]
                for n, stand_in in parts:
                    setattr(rule, n, stand_in)
                line = {"ablate": name, "shape": shape, "dtype": "bfloat16",
                        "head_chunks": chunks}
                try:
                    times(a, line)
                finally:
                    for n, f in kept:
                        setattr(rule, n, f)
                emit(line)
            kept = rule.CHUNKS_PER_STEP, rule.BWD_CHUNKS_PER_STEP
            for n in args.steps:
                rule.CHUNKS_PER_STEP = rule.BWD_CHUNKS_PER_STEP = n
                line = {"chunks_per_step": n, "shape": shape,
                        "dtype": "bfloat16", "head_chunks": chunks}
                # the backward kernel alone at n: the forward's constant back

                def forward_back():
                    rule.CHUNKS_PER_STEP = kept[0]

                times(a, line, forward_back)
                emit(line)
            rule.CHUNKS_PER_STEP, rule.BWD_CHUNKS_PER_STEP = kept
            continue
        for dtype in (jnp.dtype(d) for d in args.dtypes):
            for strong in (1.0, 60.0):
                a = inputs(jnp, jax, R, T, H, D, docs, dtype, strong)
                real = (a[5] > 0)[..., None, None]
                line = {"shape": shape, "dtype": jnp.dtype(dtype).name,
                        "decay_x": strong, "head_chunks": chunks}
                forms = {"kernel": kernel}
                if args.xla:
                    forms["xla"] = xla
                # the scan's backward keeps a state a token: 0.5 MB each
                with_grads = R * T <= 8192
                with jax.default_matmul_precision("highest"):
                    want = jax.jit(lambda *a: token_scan(jax, jnp, *a))(*a)
                    if with_grads:
                        gwant = grad_of(
                            lambda *a: token_scan(jax, jnp, *a))(*a)
                for n, f in forms.items():
                    o = jax.jit(f)(*a)
                    gs = grad_of(f)(*a)
                    line[n + "_fwd_err"] = float(jnp.max(jnp.abs(
                        (o - want) * real)))
                    line[n + "_fwd_median_rel_err"] = float(
                        jnp.median(jnp.abs(o - want)[a[5] > 0])
                        / jnp.median(jnp.abs(want)[a[5] > 0]))
                    line[n + "_finite"] = bool(all(
                        jnp.isfinite(x.astype(jnp.float32)).all()
                        for x in (o,) + tuple(gs)))
                    if with_grads:
                        line[n + "_grad_err"] = [
                            float(jnp.max(jnp.abs(x.astype(jnp.float32) - w)))
                            for x, w in zip(gs, gwant)]
                    line[n + "_fwd_ms"] = timed(jax.jit(f), a)
                    line[n + "_grad_ms"] = timed(grad_of(f), a)
                line["scale"] = float(jnp.max(jnp.abs(want)))
                if with_grads:
                    line["grad_scale"] = [float(jnp.max(jnp.abs(w)))
                                          for w in gwant]
                emit(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
