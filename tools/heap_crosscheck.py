"""Hold the compile ledger's memory record against the compiler, on the chip.

    python tools/heap_crosscheck.py --workload <cell> [--seed N] [--out F]

Runs one benchmark cell's driver IN THIS PROCESS for a few seconds (its
warm-up compiles every program; the ledger files each executable with the
engine's label and the compiler's statistics), remembers the arguments'
shapes of every grad program dispatched, and afterwards — outside the
cell's run — compiles each of them again ahead of time:
``jit(train_grad_sliced).lower(<the same shapes>).compile()
.memory_analysis()``. It prints, per grid, what the ledger filed under
that grid's label beside the ahead-of-time figures; they must be equal
(``peak_bytes - argument_bytes - output_bytes + alias_bytes`` is the heap
alive at the program's fullest moment: what resident bytes are added to).
This is the one place that compiles a program twice: the engine and the
ledger never do. Chip only (the drivers refuse another platform) but for
``--tiny``, the CPU rehearsal; exit 1 where a grid disagrees or the ledger
found no executable for one.
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
    ap.add_argument("--seed", type=int, default=5400000)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out", default=None,
                    help="also write the JSON lines to this file")
    ap.add_argument("--tiny", action="store_true",
                    help="rehearse on the CPU at benchmark/rehearse.py's "
                         "toy size")
    args = ap.parse_args()

    from benchmark import harness

    if args.tiny:
        from benchmark import rehearse

        resolved = rehearse.tiny_spec(args.workload, 0, args.seconds)
        out = resolved["out"]
    else:
        resolved = harness.resolve_cell(args.workload)
        out = os.path.join(harness.OUT_ROOT, args.workload + ".crosscheck")
        os.makedirs(out, exist_ok=True)
        resolved = {
            **resolved, "workload": args.workload, "seed": args.seed,
            "seconds": args.seconds, "trace": 0, "out": out,
            "t0": time.time(), "platform": "tpu"}
    spec_path = os.path.join(out, "spec.json")
    harness.write_json(spec_path, resolved)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax

    from areal_tpu.backend.jax_train import JaxTrainEngine
    from areal_tpu.base import compile_watch

    seen = {}  # (R, L, with carry) -> (engine, loss_fn, shapes)
    dispatch = JaxTrainEngine._dispatch_grad

    def observed(self, loss_fn, args_, carry, R, L):
        key = (R, L, carry is not None)
        if key not in seen:
            full = args_ + [carry] if carry is not None else args_
            # an uncommitted array (the uploaded grids) goes where the
            # program's committed arguments are: no sharding of its own
            seen[key] = (self, loss_fn, jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype,
                    sharding=x.sharding if x.committed else None), full))
        return dispatch(self, loss_fn, args_, carry, R, L)

    JaxTrainEngine._dispatch_grad = observed
    sys.argv = [resolved["driver"], "--spec", spec_path]
    try:
        runpy.run_path(resolved["driver"], run_name="__main__")
    except SystemExit as e:
        if e.code not in (0, None):
            # "not correct" is exit 1 too: the record is still there
            print(f"driver exit {e.code}", file=sys.stderr)
    JaxTrainEngine._dispatch_grad = dispatch

    filed = compile_watch.executables("train_grad_sliced")
    fields = {k: compile_watch.MEMORY_FIELDS[k] for k in (
        "temp_bytes", "argument_bytes", "output_bytes", "alias_bytes",
        "peak_bytes")}
    lines, bad = [], 0
    for (R, L, with_carry), (engine, loss_fn, shapes) in seen.items():
        remat = engine._remat_for(R, L)
        fn = engine._get_sliced_grad_fn(loss_fn, with_carry, R, remat)
        if not hasattr(fn, "lower"):  # under an enabled compile watch
            fn = fn.__wrapped__
        compile_watch.label("train_grad_sliced", aot=True)
        with engine._mesh_ctx():
            analysis = fn.lower(*shapes).compile().memory_analysis()
        aot = {k: int(getattr(analysis, attr)) for k, attr in fields.items()}
        mine = [r for r in filed if r["label"].get("grid") == f"{R}x{L}"
                and r["label"].get("carry") == with_carry
                and r["label"].get("remat") == remat]
        ledger = ({k: mine[-1][k] for k in fields} if mine else None)
        equal = ledger == aot
        bad += not equal
        lines.append({
            "workload": args.workload, "grid": f"{R}x{L}",
            "carry": with_carry, "remat": remat, "equal": equal,
            "ledger": ledger, "ahead_of_time": aot,
            "cache": mine[-1]["cache"] if mine else None,
            "reckoned_heap_bytes": (mine[-1]["label"].get(
                "reckoned_heap_bytes") if mine else None)})
    text = "\n".join(json.dumps(ln) for ln in lines)
    print(text, flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text + "\n")
    return 1 if bad or not lines else 0


if __name__ == "__main__":
    sys.exit(main())
