"""Chip sweep behind the gated delta rule kernels' two constants
(``ops/pallas/gated_delta_rule.CHUNKS_PER_STEP`` and the backward's
``BWD_CHUNKS_PER_STEP``, ``--chunks-a-step`` / ``--bwd-chunks-a-step``):
``models/gdn.gated_delta_rule`` alone — forward, and forward + backward —
as the XLA form and as the Pallas kernel pair at each candidate, at the
Qwen3-Next cell's rows (1 x 14,336 and 1 x 8,704, 16 key / 32 value heads
of 128, chunk 64, bfloat16, two documents a row the second of which starts
inside a chunk). Beside each time the least time the chip's peaks allow
(``benchmark/gdn_cost.gdn_rule_cost``) and the share of it.

    python tools/gdn_rule_sweep.py            (chip)
    python tools/gdn_rule_sweep.py --parity   (chip: numbers, no times)
    python tools/gdn_rule_sweep.py --compile  (here: compiles the kernels
                                               for a described v5e)
    python tools/gdn_rule_sweep.py --parts ends   (chip; --parity and
                                                   --compile as above)

``--parts ends`` times the MIXER'S ENDS alone — everything between
``gdn_conv`` and ``gdn_out_proj``: the l2 norms of q and k, the rule, the
gated RMS norm times silu(z) — from the arrays as the mixer has them
(tokens major, heads in the lanes, raw q and k): ``xla-norms`` is the
mixer's XLA text around the kernels (what ran before PR 55), ``in-kernel``
the kernels with both norms inside (``gdn.rule_with_norms``; left out on
a tree that has no such entry, so the tool runs on the parent too).

Prints one JSON line a case: device milliseconds a call from a profiler
capture of ``--reps`` calls (its largest ops, and the two kernels' own:
``gdn_rule_fwd_ms`` / ``gdn_rule_bwd_ms``). ``--parity`` instead
runs the COMPILED kernels as shipped against the XLA form on the same
operands, bfloat16 and float32 (the latter under "highest", as the cell's
``rule_error`` does), and both against the XLA form in float32: the worst
distance (max |a − b| over max |b|) of o and of each gradient — what the
CPU tests see only through Pallas's interpreter.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

G, H, D, Q = 16, 32, 128, 64
LENGTHS = (14336, 8704)
NAMES = ("o", "dq", "dk", "dv", "dg", "dbeta")
ENDS_NAMES = ("y", "dq", "dk", "dv", "dg", "dbeta", "dz", "dw")
EPS = 1e-6  # the configuration's rms_norm_eps


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--chunks-a-step", type=int, nargs="*",
                    default=[2, 4, 8])
    ap.add_argument("--bwd-chunks-a-step", type=int, nargs="*", default=[],
                    help="the backward kernel's own chunks a step (a tree "
                         "that has ``BWD_CHUNKS_PER_STEP``); default: as "
                         "shipped")
    ap.add_argument("--lengths", type=int, nargs="*", default=list(LENGTHS))
    ap.add_argument("--skip-xla", action="store_true")
    ap.add_argument("--parts", choices=("rule", "ends"), default="rule")
    ap.add_argument("--parity", action="store_true")
    ap.add_argument("--compile", action="store_true")
    ap.add_argument("--low", action="store_true",
                    help="draw A_log low: a state that lasts (parity)")
    ap.add_argument("--out", default="chiprun_out/gdn_rule_sweep.jsonl")
    a = ap.parse_args()
    if a.compile:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ssd_scan_sweep import device_ms

    from areal_tpu.models import gdn
    from areal_tpu.ops.pallas import gated_delta_rule as kernel
    from benchmark import gdn_cost, peaks

    chip = None
    if a.compile:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        chip = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
        kind = "TPU v5e"
    else:
        kind = jax.devices()[0].device_kind
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    out = open(a.out, "a")

    def emit(**rec):
        line = json.dumps(rec)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    bf, f32 = jnp.bfloat16, jnp.float32
    shipped = (kernel.CHUNKS_PER_STEP,
               getattr(kernel, "BWD_CHUNKS_PER_STEP", None))

    def chunks_a_step(fwd=None, bwd=None):
        """Set the kernels' constants (None: as shipped; an older tree has
        the forward's alone, which its backward follows)."""
        kernel.CHUNKS_PER_STEP = fwd or shipped[0]
        if shipped[1] is not None:
            kernel.BWD_CHUNKS_PER_STEP = bwd or shipped[1]
        jax.clear_caches()
        return dict(fwd_chunks_a_step=kernel.CHUNKS_PER_STEP,
                    bwd_chunks_a_step=getattr(
                        kernel, "BWD_CHUNKS_PER_STEP", kernel.CHUNKS_PER_STEP))

    def kernel_ms(ops):
        """The two kernels' own device ms a call, from a capture's largest
        ops."""
        return {f"{name}_ms": sum(ms for op, ms in ops.items()
                                  if op.split(".")[0] == name)
                for name in (kernel.FWD_NAME, kernel.BWD_NAME)}

    def worst(got, want):
        return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))

    def ends_fn(how, impl, seg, dtype=bf, grads=True, aux=False):
        """The mixer's ends from flat arrays, jitted: y, or the gradients
        of Σ wt · y (``aux``: with y) — the mixer's XLA text around
        ``gdn.gated_delta_rule(impl)`` ("xla-norms") or
        ``gdn.rule_with_norms`` ("in-kernel")."""
        def loss(q, k, v, g, beta, z, w, wt):
            T = q.shape[1]
            q, k, v, z, w = (x.astype(dtype) for x in (q, k, v, z, w))
            q, k = (x.reshape(1, T, G, D) for x in (q, k))
            v = v.reshape(1, T, H, D)
            if how == "in-kernel":
                y = gdn.rule_with_norms(q, k, v, z, w, g, beta, seg, Q, EPS,
                                        impl)
            else:
                q = (gdn.l2_normalize(q) * D ** -0.5).astype(dtype)
                k = gdn.l2_normalize(k).astype(dtype)
                o = gdn.gated_delta_rule(q, k, v, g, beta, seg, Q, impl)
                o = o * jax.lax.rsqrt(
                    jnp.mean(o * o, axis=-1, keepdims=True) + EPS)
                y = (o * w.astype(f32)).astype(dtype)
                y = (y.reshape(1, T, H * D).astype(f32)
                     * jax.nn.silu(z.astype(f32))).astype(dtype)
            return jnp.sum(y.astype(f32) * wt), y

        if not grads:
            return jax.jit(lambda *xs: loss(*xs)[1])
        if aux:
            return jax.jit(jax.value_and_grad(loss, argnums=tuple(range(7)),
                                              has_aux=True))
        return jax.jit(jax.grad(lambda *xs: loss(*xs)[0],
                                argnums=tuple(range(7))))

    def ends(T, v, g, beta, seg, key):
        """``--parts ends`` at one row length."""
        ks = jax.random.split(key, 5)
        q, k = (jax.nn.silu(2.0 * jax.random.normal(kk, (1, T, G * D))
                            ).astype(bf) for kk in ks[:2])
        z = (2.0 * jax.random.normal(ks[2], (1, T, H * D))).astype(bf)
        w = (1.0 + 0.3 * jax.random.normal(ks[3], (D,))).astype(bf)
        wt = jax.random.normal(ks[4], (1, T, H * D))
        args = (q, k, v.reshape(1, T, H * D), g, beta, z, w, wt)
        impl = ("pallas" if a.compile or jax.default_backend() == "tpu"
                else "pallas_interpret")
        hows = ["xla-norms"] + (
            ["in-kernel"] if hasattr(gdn, "rule_with_norms") else [])
        if a.parity:
            def y_and_grads(how, impl, dtype):
                (_, y), grads = ends_fn(how, impl, seg, dtype, aux=True)(
                    *args)
                return [np.asarray(x, np.float32) for x in (y, *grads)]

            with jax.default_matmul_precision("highest"):
                exact = y_and_grads("xla-norms", "xla", f32)
            got = {how: y_and_grads(how, impl, bf) for how in hows}
            emit(length=T, parts="ends", impl=impl, low=a.low,
                 finite={how: all(bool(np.isfinite(x).all()) for x in xs)
                         for how, xs in got.items()},
                 **{f"{how}_vs_float32": dict(zip(
                     ENDS_NAMES, (worst(x, e) for x, e in zip(xs, exact))))
                    for how, xs in got.items()})
            return
        for how, bwd in [(how, n) for how in hows
                         for n in (a.bwd_chunks_a_step or [None])]:
            rec = dict(length=T, parts="ends", impl=how, **chunks_a_step(
                bwd=bwd))
            if a.compile:
                shapes = [jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=chip) for x in args]
                t = time.perf_counter()
                c = ends_fn(how, impl, seg).lower(*shapes).compile()
                emit(**rec, compile_s=time.perf_counter() - t,
                     kernels=c.as_text().count("tpu_custom_call"),
                     temp_mb=c.memory_analysis().temp_size_in_bytes / 1e6)
                continue
            f, f_ops = device_ms(ends_fn(how, impl, seg, grads=False), args,
                                 a.reps)
            fb, fb_ops = device_ms(ends_fn(how, impl, seg), args, a.reps)
            emit(**rec, fwd_ms=f, fwd_bwd_ms=fb, **kernel_ms(fb_ops),
                 fwd_ops=f_ops, fwd_bwd_ops=fb_ops)

    for T in a.lengths:
        ks = jax.random.split(jax.random.PRNGKey(0), 7)
        q = (gdn.l2_normalize(jax.random.normal(ks[0], (1, T, G, D)))
             * D ** -0.5).astype(bf)
        k = gdn.l2_normalize(jax.random.normal(ks[1], (1, T, G, D))).astype(bf)
        v = jax.random.normal(ks[2], (1, T, H, D)).astype(bf)
        lo, hi = (0.01, 0.3) if a.low else (1.0, 16.0)
        g = -jax.random.uniform(ks[3], (H,), minval=lo, maxval=hi
                                ) * jax.nn.softplus(
            jax.random.normal(ks[4], (1, T, H)) + 1.0)
        beta = jax.nn.sigmoid(jax.random.normal(ks[5], (1, T, H)))
        w = jax.random.normal(ks[6], (1, T, H, D))
        cut = (T * 4 // 5) // Q * Q + 23  # the second document's start
        seg = jnp.asarray(np.where(np.arange(T) < cut, 1, 2), jnp.int32)[None]
        args = (q, k, v, g, beta, w)
        if a.parts == "ends":
            ends(T, v, g, beta, seg, jax.random.PRNGKey(T))
            continue

        def fwd(impl):
            return jax.jit(lambda q, k, v, g, beta, w: gdn.gated_delta_rule(
                q, k, v, g, beta, seg, Q, impl))

        def both(impl, dtype=bf, aux=False):
            def loss(q, k, v, g, beta, w):
                o = gdn.gated_delta_rule(q.astype(dtype), k.astype(dtype),
                                         v.astype(dtype), g, beta, seg, Q,
                                         impl)
                return jnp.sum(o * w), o

            if aux:
                return jax.jit(jax.value_and_grad(
                    loss, argnums=(0, 1, 2, 3, 4), has_aux=True))
            return jax.jit(jax.grad(lambda *xs: loss(*xs)[0],
                                    argnums=(0, 1, 2, 3, 4)))

        if a.parity:
            how = ("pallas" if jax.default_backend() == "tpu"
                   else "pallas_interpret")

            def o_and_grads(impl, dtype):
                (_, o), grads = both(impl, dtype, aux=True)(*args)
                return [np.asarray(x, np.float32) for x in (o, *grads)]

            got, xla = o_and_grads(how, bf), o_and_grads("xla", bf)
            with jax.default_matmul_precision("highest"):
                got32 = o_and_grads(how, jnp.float32)
                exact = o_and_grads("xla", jnp.float32)
            emit(length=T, impl=how, low=a.low,
                 finite=all(bool(np.isfinite(x).all()) for x in got + got32),
                 kernel_vs_xla=dict(zip(NAMES, map(worst, got, xla))),
                 kernel_vs_float32=dict(zip(NAMES, map(worst, got, exact))),
                 xla_vs_float32=dict(zip(NAMES, map(worst, xla, exact))),
                 float32_kernel_vs_float32=dict(zip(NAMES, map(worst, got32,
                                                               exact))))
            continue
        least = {}
        for backward in (False, True):
            ops, nbytes = gdn_cost.gdn_rule_cost(1, T, G, H, D, D, backward)
            least[backward] = 1e3 * peaks.least_time(ops, nbytes, kind)[0]
        cases = ([] if a.skip_xla else [("xla", "xla", None, None)]) + [
            (f"pallas-{n}" + (f"-bwd-{m}" if m else ""), "pallas", n, m)
            for n in a.chunks_a_step
            for m in (a.bwd_chunks_a_step or [None])]
        for label, impl, n, m in cases:
            rec = dict(length=T, impl=label)
            if n is not None:
                rec.update(chunks_a_step(n, m))
            if a.compile:
                if impl != "pallas":
                    continue
                shapes = [jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=chip) for x in args]
                t = time.perf_counter()
                c = both(impl).lower(*shapes).compile()
                emit(**rec, compile_s=time.perf_counter() - t,
                     kernels=c.as_text().count("tpu_custom_call"),
                     temp_mb=c.memory_analysis().temp_size_in_bytes / 1e6)
                continue
            try:
                f, f_ops = device_ms(fwd(impl), args, a.reps)
                fb, fb_ops = device_ms(both(impl), args, a.reps)
            except Exception as e:  # a step that does not fit VMEM
                emit(**rec, error=str(e)[-400:])
                continue
            emit(**rec, fwd_ms=f, fwd_bwd_ms=fb, least_fwd_ms=least[False],
                 least_fwd_bwd_ms=least[False] + least[True],
                 fwd_roofline_pct=100 * least[False] / f,
                 fwd_bwd_roofline_pct=100 * (least[False] + least[True]) / fb,
                 **(kernel_ms(fb_ops) if impl == "pallas" else {}),
                 fwd_ops=f_ops, fwd_bwd_ops=fb_ops)
    return 0


if __name__ == "__main__":
    sys.exit(main())
