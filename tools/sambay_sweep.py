"""Chip sweep behind two choices of the phi4flash (SambaY) path, at the
cell's rows (1 x 8192, 40 / 20 heads of 64, d_inner 5120 x 16 states):

 - differential attention as ONE kernel call a layer (both softmaxes of
   every pair as 40 heads against a value of 128: q and k zero-padded to
   the 128 lanes the kernels pad a head of 64 to anyway,
   ``ops/attention.packed_attention``) against FOUR calls at 64 (the
   family's flash-diff form: q1/q2 x v1/v2), forward + backward, for the
   causal kernel (F, X) and the windowed one (S, window 512);
 - the selective scan: the Pallas kernels (``ops/pallas/selective_scan``)
   forward, and forward + backward.

    python tools/sambay_sweep.py            (chip)

Prints one JSON line a case: milliseconds, the best of ``--reps``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def best_ms(fn, args, reps: int) -> float:
    import jax

    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t)
    return 1e3 * min(times)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--length", type=int, default=8192)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", default="chiprun_out/sambay_sweep.jsonl")
    ap.add_argument("--scan-only", action="store_true")
    ap.add_argument("--unroll", type=int, nargs="*", default=[],
                    help="time the scan at each of these loop unrolls "
                         "(ops/pallas/selective_scan.UNROLL)")
    ap.add_argument("--tiles", nargs="*", default=[],
                    help="... and at each CHUNKxD_TILE given")
    a = ap.parse_args()
    import jax
    import jax.numpy as jnp

    from areal_tpu.models import ssm
    from areal_tpu.ops import attention as att

    T, H, Hkv, D = a.length, 40, 20, 64
    k = jax.random.split(jax.random.PRNGKey(0), 8)
    bf = jnp.bfloat16
    q = jax.random.normal(k[0], (1, T, H, D), bf)
    kk = jax.random.normal(k[1], (1, T, Hkv, D), bf)
    v = jax.random.normal(k[2], (1, T, Hkv, D), bf)
    seg = jnp.ones((1, T), jnp.int32)
    lines = []

    def emit(**kw):
        lines.append(kw)
        print(json.dumps(kw), flush=True)

    for name, window in (() if a.scan_only else (
            ("causal", None), ("window512", 512))):
        def attend(q, k, v):
            return att.packed_attention(q, k, v, seg, seg, causal=True,
                                        sliding_window=window, impl="pallas")

        def one_call(q, k, v):
            o = attend(att.differential_q(q, Hkv), k, att.differential_v(v))
            return jnp.sum(o.astype(jnp.float32) ** 2)

        def four_calls(q, k, v):
            q = q.reshape(1, T, H // 2, 2, D)
            k = k.reshape(1, T, Hkv // 2, 2, D)
            v = v.reshape(1, T, Hkv // 2, 2, D)
            tot = 0.0
            for i in range(2):
                for j in range(2):
                    o = attend(q[:, :, :, i], k[:, :, :, i], v[:, :, :, j])
                    tot = tot + jnp.sum(o.astype(jnp.float32) ** 2)
            return tot

        for form, fn in (("one_call_40x128", one_call),
                         ("four_calls_20x64", four_calls)):
            emit(case=f"{name}/{form}", length=T,
                 fwd_ms=best_ms(jax.jit(fn), (q, kk, v), a.reps),
                 fwd_bwd_ms=best_ms(jax.jit(jax.grad(fn, argnums=(0, 1, 2))),
                                    (q, kk, v), a.reps))

    Dn, N = 5120, 16
    x = jax.random.normal(k[3], (1, T, Dn), bf)
    dt = jax.nn.softplus(jax.random.normal(k[4], (1, T, Dn))) * 0.1
    A = -jnp.exp(jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)))[None] * (
        jnp.ones((Dn, 1)))
    Bm = jax.random.normal(k[5], (1, T, N), bf)
    Cm = jax.random.normal(k[6], (1, T, N), bf)
    Dk = jnp.ones((Dn,))
    seg2 = jnp.concatenate([jnp.ones((1, T // 3), jnp.int32),
                            jnp.full((1, T - T // 3), 2, jnp.int32)], 1)

    def scan(x, dt, A, Bm, Cm, Dk):
        return jnp.sum(ssm.selective_scan(x, dt, A, Bm, Cm, Dk, seg2,
                                          "pallas") ** 2)

    args = (x, dt, A, Bm, Cm, Dk)
    from areal_tpu.ops.pallas import selective_scan as kernel

    settings = [(kernel.UNROLL, kernel.CHUNK, kernel.D_TILE)]
    settings += [(u, kernel.CHUNK, kernel.D_TILE) for u in a.unroll]
    settings += [(kernel.UNROLL, *map(int, t.split("x"))) for t in a.tiles]
    for unroll, chunk, tile in settings:
        kernel.UNROLL, kernel.CHUNK, kernel.D_TILE = unroll, chunk, tile
        ssm.S6_CHUNK = chunk
        jax.clear_caches()
        try:
            emit(case="selective_scan/pallas", length=T, d_inner=Dn, state=N,
                 unroll=unroll, chunk=chunk, d_tile=tile,
                 fwd_ms=best_ms(jax.jit(scan), args, a.reps),
                 fwd_bwd_ms=best_ms(
                     jax.jit(jax.grad(scan, argnums=(0, 1, 2, 3, 4))), args,
                     a.reps))
        except Exception as e:  # noqa: BLE001 — a tile the chip refuses
            emit(case="selective_scan/pallas", unroll=unroll, chunk=chunk,
                 d_tile=tile, error=str(e)[:300])
    os.makedirs(os.path.dirname(a.out), exist_ok=True)
    with open(a.out, "a") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
