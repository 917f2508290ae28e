#!/usr/bin/env python3
"""The ENGINE's own grad programs of a train cell at full size, compiled
for a described v5e — no chip: the temporaries the compile ledger will file
on the chip (``grad_program_heap_gb``), before a chip run is spent on them.

    python tools/grad_program_described.py \\
        --workload kimi-linear-48b-a3b.train-math-cot-16k \\
        --grids 2x7552x4:full 1x10752x6:attention [--dump DIR]

A grid is ``ROWSxLENGTHxMICRO-BATCHES:REMAT-ENTRY`` as the cell's
``remat_plan`` names it (the micro-batches of a step's batch that share the
grid: the uploaded grids are [micro-batches · rows, length]). The tool runs
the cell's driver at ``benchmark/rehearse.py``'s toy size on the CPU once,
to have the driver's own engine, loss function and argument tree; then it
hands that engine the configuration's full-size ``TransformerConfig``, the
parameters as shapes on the described chip and ``attn_impl="pallas"``, and
lowers ``train_grad_sliced`` without and with the carry at the real shapes
(what ``tools/heap_crosscheck.py`` does on the chip with the real arrays).
One JSON line a grid: ``temp_gb_nocarry`` / ``temp_gb_carry``. In PR 65 the
carried program at 2 x 7,552 read 6.47461632 GB here and on the chip, where
a compile of the model's forward + backward alone (``tests/
test_tpu_compile.py``, child ``kimi``) had said 5.08: the head, the loss
and the carry move the schedule, and XLA's ``temp_bytes`` follows the
schedule. ``--dump DIR`` leaves XLA's buffer assignment of both programs
there (``*buffer-assignment.txt``: every value's size and offset in the
heap). Cells on ``benchmark/sharelib.py`` (one chip, no mesh).
"""

from __future__ import annotations

import argparse
import json
import os
import runpy
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--grids", nargs="+", required=True)
    ap.add_argument("--dump", default=None)
    args = ap.parse_args()
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if args.dump:
        os.environ["XLA_FLAGS"] = (
            f"--xla_dump_to={args.dump} --xla_dump_hlo_as_text=true "
            "--xla_dump_hlo_module_re=.*train_grad_sliced.*")
    os.chdir(ROOT)

    from benchmark import harness, rehearse, weights

    # ---- the driver's own engine, loss function and argument tree
    resolved = rehearse.tiny_spec(args.workload, 0, 3.0)
    spec_path = os.path.join(resolved["out"], "spec.json")
    harness.write_json(spec_path, resolved)

    import jax
    import jax.numpy as jnp

    from areal_tpu.backend.jax_train import JaxTrainEngine
    from areal_tpu.models import transformer

    seen = []
    dispatch = JaxTrainEngine._dispatch_grad

    def observed(self, loss_fn, args_, carry, R, L):
        if not seen:
            seen.append((self, loss_fn, args_))
        return dispatch(self, loss_fn, args_, carry, R, L)

    JaxTrainEngine._dispatch_grad = observed
    sys.argv = [resolved["driver"], "--spec", spec_path]
    try:
        runpy.run_path(resolved["driver"], run_name="__main__")
    except SystemExit:
        pass  # "not correct" at the toy size is no one's concern here
    JaxTrainEngine._dispatch_grad = dispatch
    engine, loss_fn, toy = seen[0]
    jax.clear_caches()

    # ---- the same engine at the configuration's full size, on shapes
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    cfg = weights.model_config(harness.resolve_cell(args.workload)["config"])
    shapes = jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
    engine.cfg, engine.attn_impl, engine.mesh = cfg, "pallas", None
    engine._params = jax.tree.map(
        lambda x: on_chip(x.shape, jnp.float32), shapes)
    engine._grad_fns = {}
    compute = jax.tree.map(
        lambda x: on_chip(x.shape, engine.compute_dtype), shapes)

    def jitted(with_carry, R, remat):
        fn = engine._get_sliced_grad_fn(loss_fn, with_carry, R, remat)
        return fn if hasattr(fn, "lower") else fn.__wrapped__

    for grid in args.grids:
        dims, _, remat = grid.partition(":")
        R, L, n = (int(v) for v in dims.split("x"))
        ops = [compute,
               {k: on_chip((n * R, L), v.dtype) for k, v in toy[1].items()},
               {k: on_chip((n,) + v.shape[1:], v.dtype)
                for k, v in toy[2].items()}]
        ops += [on_chip((), jnp.asarray(v).dtype) for v in toy[3:7]]
        began = time.time()
        plain = jitted(False, R, remat or False)
        temp = {"nocarry": plain.lower(*ops).compile().memory_analysis()
                .temp_size_in_bytes}
        carry = jax.tree.map(lambda x: on_chip(x.shape, x.dtype),
                             jax.eval_shape(plain, *ops))
        carried = jitted(True, R, remat or False).lower(
            *ops, carry).compile()
        temp["carry"] = carried.memory_analysis().temp_size_in_bytes
        print(json.dumps({
            "workload": args.workload, "grid": f"{R}x{L}", "micro_batches": n,
            "remat": remat or None, "device": "described v5e",
            **{f"temp_gb_{k}": v / 1e9 for k, v in temp.items()},
            "custom_calls": carried.as_text().count("tpu_custom_call"),
            "seconds": round(time.time() - began, 1)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
