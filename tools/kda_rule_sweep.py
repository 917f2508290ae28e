#!/usr/bin/env python3
"""The delta rule with a decay a key channel (ops/pallas/kda_rule.py) on
the chip: the compiled kernel pair against the XLA form of the same chunk
algebra (models/kda._rule_xla) and against
the recurrence a token at a time, and forward / forward + backward times
of both forms at the Kimi-Linear cell's grids.

    chiprun -- python tools/kda_rule_sweep.py            # parity + times
    python tools/kda_rule_sweep.py --compile             # described v5e, here

One JSON line a case on stdout (appended to chiprun_out/kda_rule_sweep.jsonl).
Times are a host clock around ``--iters`` calls in a row, the device
finished (a call is tens of milliseconds: the ~0.7 ms round trip is in
the noise); heads run a group of 8 at a time, as the mixer runs them.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def inputs(jnp, jax, T, H, D, docs, dtype, strong, seed=0):
    from areal_tpu.models import gdn

    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = (gdn.l2_normalize(jax.random.normal(ks[0], (1, T, H, D)))
         * D ** -0.5).astype(dtype)
    k = gdn.l2_normalize(jax.random.normal(ks[1], (1, T, H, D))).astype(dtype)
    v = jax.random.normal(ks[2], (1, T, H, D)).astype(dtype)
    g = -jax.nn.softplus(jax.random.normal(ks[3], (1, T, H, D)) - 3) * strong
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, T, H)))
    starts = [0]
    for n in docs:
        starts.append(starts[-1] + n)
    pos = jnp.arange(T)
    seg = sum((pos >= s).astype(jnp.int32) for s in starts[:-1])
    seg = jnp.where(pos < starts[-1], seg, 0)[None]
    return q, k, v, g, beta, seg


def token_scan(jax, jnp, q, k, v, g, beta, seg):
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    H, dk = q.shape[2:]

    def step(carry, x):
        S, prev = carry
        q, k, v, g, b, s = x
        S = jnp.where(s != prev, 0.0, S)
        S = jnp.exp(g)[:, :, None] * S
        d = b[:, None] * (v - jnp.einsum("hkv,hk->hv", S, k))
        S = S + k[:, :, None] * d[:, None, :]
        return (S, s), jnp.einsum("hkv,hk->hv", S, q)

    _, o = jax.lax.scan(step, (jnp.zeros((H, dk, dk)), jnp.int32(-1)),
                        (q[0], k[0], v[0], g[0], beta[0], seg[0]))
    return o[None]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--compile", action="store_true")
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--shapes", nargs="*", default=[
        "8192:1658,6480", "7552:5062,1682"])
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp

    from areal_tpu.models import gdn, kda

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
        print(json.dumps(line), flush=True)
        with open("chiprun_out/kda_rule_sweep.jsonl", "a") as f:
            f.write(json.dumps(line) + "\n")

    def loss(fn):
        return lambda q, k, v, g, b, seg: jnp.sum(
            jnp.sin(fn(q, k, v, g, b, seg).astype(jnp.float32)))

    def kernel(q, k, v, g, b, seg):
        return kda.channel_decay_rule(q, k, v, g, b, seg, 64, "pallas")

    def xla(q, k, v, g, b, seg):
        return kda.channel_decay_rule(q, k, v, g, b, seg, 64, "xla")

    for shape in args.shapes:
        T, docs = shape.split(":")
        T, docs = int(T), [int(x) for x in docs.split(",")]
        for dtype in (jnp.bfloat16, jnp.float32):
            name = jnp.dtype(dtype).name
            if args.compile:
                sds = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in (
                    ((1, T, H, D), dtype), ((1, T, H, D), dtype),
                    ((1, T, H, D), dtype), ((1, T, H, D), jnp.float32),
                    ((1, T, H), jnp.float32), ((1, T), jnp.int32))]
                t0 = time.time()
                c = jax.jit(jax.value_and_grad(
                    loss(kernel), argnums=(0, 1, 2, 3, 4))).lower(
                        *sds).compile()
                emit({"compile": shape, "dtype": name,
                      "seconds": round(time.time() - t0, 2),
                      "temp_bytes": c.memory_analysis().temp_size_in_bytes,
                      "kernels": [n for n in ("kda_rule_fwd", "kda_rule_bwd")
                                  if n in c.as_text()]})
                continue
            for strong in (1.0, 60.0):
                a = inputs(jnp, jax, T, H, D, docs, dtype, strong)
                real = (a[5] > 0)[..., None, None]
                line = {"shape": shape, "dtype": name, "decay_x": strong}
                fwd = {"kernel": jax.jit(kernel), "xla": jax.jit(xla)}
                grad = {n: jax.jit(jax.grad(loss(f), argnums=(0, 1, 2, 3, 4)))
                        for n, f in (("kernel", kernel), ("xla", xla))}
                with jax.default_matmul_precision("highest"):
                    want = jax.jit(lambda *a: token_scan(jax, jnp, *a))(*a)
                    gwant = jax.jit(jax.grad(
                        loss(lambda *a: token_scan(jax, jnp, *a)),
                        argnums=(0, 1, 2, 3, 4)))(*a)
                for n in fwd:
                    o = fwd[n](*a)
                    gs = grad[n](*a)
                    line[n + "_fwd_err"] = float(jnp.max(jnp.abs(
                        (o - want) * real)))
                    line[n + "_finite"] = bool(all(
                        jnp.isfinite(x.astype(jnp.float32)).all()
                        for x in (o,) + tuple(gs)))
                    line[n + "_grad_err"] = [float(jnp.max(jnp.abs(
                        x.astype(jnp.float32) - w))) for x, w in zip(gs, gwant)]
                    for what, f in (("fwd", fwd[n]), ("grad", grad[n])):
                        jax.block_until_ready(f(*a))
                        t0 = time.perf_counter()
                        for _ in range(args.iters):
                            out = f(*a)
                        jax.block_until_ready(out)
                        line[f"{n}_{what}_ms"] = round(
                            1e3 * (time.perf_counter() - t0) / args.iters, 3)
                line["scale"] = float(jnp.max(jnp.abs(want)))
                line["grad_scale"] = [float(jnp.max(jnp.abs(w)))
                                      for w in gwant]
                emit(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
