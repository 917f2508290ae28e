"""Chip sweep behind the Mamba-2 (SSD) scan kernel's one constant
(``ops/pallas/ssd_scan.HEADS_PER_STEP``): ``models/ssm.ssd_scan`` alone —
forward, and forward + backward — as the XLA einsums and as the Pallas
kernels at each heads-a-step candidate, at the two cells' geometries
(Granite: 1 x 8192 and 1 x 7040 — no whole number of chunks —, 32 heads of
64, chunk 256; Nemotron: 1 x 4096, 16 heads of 64, chunk 128; one group of
128 states, bfloat16), the row cut into ``--documents`` documents. Beside
each time the least time the chip's peaks allow
(``benchmark/ssm_cost.ssd_scan_cost``) and the share of it.

    python tools/ssd_scan_sweep.py            (chip)
    python tools/ssd_scan_sweep.py --parity   (chip: numbers, no times)
    python tools/ssd_scan_sweep.py --compile  (here: compiles the kernels
                                               for a described v5e)

Prints one JSON line a case: device milliseconds a call from a profiler
capture of ``--reps`` calls (and its largest ops), and the host clock's
best of ``--reps`` beside them. ``--parity`` instead runs the COMPILED
kernels as shipped against the XLA form on the same bfloat16 operands, and
both against the XLA form in float32: the worst distance (max |a − b| over
max |b|) of y and of each gradient — what the CPU tests see only through
Pallas's interpreter. Its row holds documents that end exactly at a
chunk's end, twice in one chunk, inside a chunk, and in trailing padding.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# (name, rows, length, heads, head_dim, groups, state, chunk)
GEOMETRIES = (("granite", 1, 8192, 32, 64, 1, 128, 256),
              ("granite-7040", 1, 7040, 32, 64, 1, 128, 256),  # 27.5 chunks
              ("nemotron", 1, 4096, 16, 64, 1, 128, 128))


def best_ms(fn, args, reps: int) -> float:
    import jax

    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t)
    return 1e3 * min(times)


def device_ms(fn, args, reps: int, top: int = 8):
    """(device milliseconds a call, {op: ms a call} of the ``top`` largest)
    from a profiler capture of ``reps`` calls: the sum of the device's
    ``XLA Ops`` events — what a host clock around one call cannot give (a
    call's round trip is ~0.7 ms here, more than a scan)."""
    import glob
    import tempfile

    import jax

    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for _ in range(reps):
                out = fn(*args)
            jax.block_until_ready(out)
        path = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                      "*.xplane.pb"))[0]
        data = jax.profiler.ProfileData.from_file(path)
    ops = {}
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                name = ev.name.split(" = ")[0].lstrip("%")
                ops[name] = ops.get(name, 0.0) + ev.duration_ns / 1e6 / reps
    largest = dict(sorted(ops.items(), key=lambda kv: -kv[1])[:top])
    return sum(ops.values()), {k: round(v, 4) for k, v in largest.items()}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--heads-a-step", type=int, nargs="*",
                    default=[8, 16, 32])
    ap.add_argument("--documents", type=int, default=3)
    ap.add_argument("--parity", action="store_true",
                    help="distances of the shipped kernels' y and "
                         "gradients from the XLA form's, no times")
    ap.add_argument("--compile", action="store_true",
                    help="no chip: compile for a described v5e, no times")
    ap.add_argument("--out", default="chiprun_out/ssd_scan_sweep.jsonl")
    a = ap.parse_args()
    if a.compile:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from areal_tpu.models import ssm
    from areal_tpu.ops.pallas import ssd_scan as kernel
    from benchmark import peaks, ssm_cost

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

    for name, R, T, H, P, G, N, Q in GEOMETRIES:
        ks = jax.random.split(jax.random.PRNGKey(0), 6)
        bf = jnp.bfloat16
        # x and y as the mixer holds them: [R, T, heads · P]
        x = jax.random.normal(ks[0], (R, T, H * P)).astype(bf)
        dt = jax.nn.softplus(jax.random.normal(ks[1], (R, T, H)) - 4.0)
        A = -jax.random.uniform(ks[2], (H,), minval=1.0, maxval=16.0)
        Bm = jax.random.normal(ks[3], (R, T, G, N)).astype(bf)
        Cm = jax.random.normal(ks[4], (R, T, G, N)).astype(bf)
        w = jax.random.normal(ks[5], (R, T, H * P))
        # equal documents, then 64 tokens of padding (segment 0)
        seg = np.arange(T) * a.documents // (T - 64) + 1
        seg = np.where(seg > a.documents, 0, seg)
        if a.parity:  # ends at a chunk's end, twice in one chunk, inside
            cuts = [5 * Q, 5 * Q + 5, 5 * Q + 40, T // 2 + 7, T - 64]
            seg = np.searchsorted(cuts, np.arange(T), side="right") + 1
            seg = np.where(seg > len(cuts), 0, seg)
        seg = jnp.asarray(seg, jnp.int32)[None].repeat(R, 0)
        args = (x, dt, A, Bm, Cm, w)
        def scan(impl, x, dt, A, Bm, Cm):
            return ssm.ssd_scan(x.reshape(R, T, H, P), dt, A, Bm, Cm, seg,
                                Q, impl).reshape(R, T, H * P)

        def fwd(impl):
            return jax.jit(lambda x, dt, A, Bm, Cm, w: scan(
                impl, x, dt, A, Bm, Cm))

        def both(impl):
            def loss(x, dt, A, Bm, Cm, w):
                return jnp.sum(scan(impl, x, dt, A, Bm, Cm) * w)

            return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))

        if a.parity:
            live = (seg > 0)[..., None]

            def y_and_grads(impl, dtype):
                def loss(x, dt, A, Bm, Cm):
                    y = scan(impl, x.astype(dtype), dt, A, Bm.astype(dtype),
                             Cm.astype(dtype)) * live
                    return jnp.sum(y * w), y

                (_, y), grads = jax.jit(jax.value_and_grad(
                    loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(*args[:5])
                return [np.asarray(v, np.float32) for v in (y, *grads)]

            def worst(got, want):
                return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))

            # no TPU: the interpreter (a rehearsal of this tool, not a reading)
            how = ("pallas" if jax.default_backend() == "tpu"
                   else "pallas_interpret")
            got = y_and_grads(how, bf)
            xla = y_and_grads("reference", bf)
            # float32 operands AND float32 passes of the MXU (its default
            # rounds float32 operands to bfloat16: the same numbers again)
            with jax.default_matmul_precision("highest"):
                exact = y_and_grads("reference", jnp.float32)
            names = ("y", "dx", "ddt", "dA", "dB", "dC")
            emit(geometry=name, rows=R, length=T, heads=H, chunk=Q,
                 heads_a_step=kernel.heads_per_step(H, P, G), impl=how,
                 finite=all(bool(np.isfinite(v).all()) for v in got),
                 kernel_vs_xla=dict(zip(names, map(worst, got, xla))),
                 kernel_vs_float32=dict(zip(names, map(worst, got, exact))),
                 xla_vs_float32=dict(zip(names, map(worst, xla, exact))))
            continue
        least = {}
        for backward in (False, True):
            ops, nbytes = ssm_cost.ssd_scan_cost(R, T, Q, H, P, G, N,
                                                 backward)
            least[backward] = 1e3 * peaks.least_time(ops, nbytes, kind)[0]
        cases = [("xla", "reference", None)] + [
            (f"pallas-{hb}", "pallas", hb) for hb in a.heads_a_step
            if H % hb == 0]
        for label, impl, hb in cases:
            if hb is not None:
                kernel.HEADS_PER_STEP = hb
                jax.clear_caches()
            rec = dict(geometry=name, rows=R, length=T, heads=H, chunk=Q,
                       impl=label, heads_a_step=(
                           kernel.heads_per_step(H, P, G) if hb else None))
            if a.compile:
                if impl != "pallas":
                    continue
                shapes = [jax.ShapeDtypeStruct(v.shape, v.dtype,
                                               sharding=chip) for v in args]
                t = time.perf_counter()
                c = both(impl).lower(*shapes).compile()
                emit(**rec, compile_s=time.perf_counter() - t,
                     kernels=c.as_text().count("tpu_custom_call"),
                     temp_mb=c.memory_analysis().temp_size_in_bytes / 1e6)
                continue
            try:
                host = (best_ms(fwd(impl), args, a.reps),
                        best_ms(both(impl), args, a.reps))
                f, f_ops = device_ms(fwd(impl), args, a.reps)
                fb, fb_ops = device_ms(both(impl), args, a.reps)
            except Exception as e:  # a tile that does not fit VMEM
                emit(**rec, error=str(e)[-400:])
                continue
            emit(**rec, fwd_ms=f, fwd_bwd_ms=fb, least_fwd_ms=least[False],
                 least_fwd_bwd_ms=least[False] + least[True],
                 fwd_roofline_pct=100 * least[False] / f,
                 fwd_bwd_roofline_pct=100 * (least[False] + least[True]) / fb,
                 host_fwd_ms=host[0], host_fwd_bwd_ms=host[1],
                 fwd_ops=f_ops, fwd_bwd_ops=fb_ops)
    return 0


if __name__ == "__main__":
    sys.exit(main())
