"""One run of one benchmark cell:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Resolves the cell's configuration, traffic mix and driver by the names in
``BENCHMARK.json``, runs the driver as a child process tree, and prints as
the LAST stdout line one JSON object with ``correct``, ``attempted``,
``failed``, ``metrics`` and ``device`` (and ``breakdown`` in a traced
run). ``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1``
its per-layer metrics. Anything else goes to stderr or to files under
``benchmark/.out/<workload>/``.

This process never imports jax: a chip belongs to one process. It exits
non-zero and prints no result line when the driver finds no TPU, fewer
chips than the cell asks for, or fails in any other way.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

T0 = time.time()  # set-up is counted from here

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402

# The contract allows a warm run 360 s and a compiling one 1200 s.
DEADLINE_SECS = 1150.0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(harness.ROOT, "areal_tpu")):
        harness.log("the program (areal_tpu/) is not beside the benchmark")
        return 2
    resolved = harness.resolve_cell(args.workload)
    out = os.path.join(harness.OUT_ROOT, args.workload)
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    spec = {
        **resolved, "workload": args.workload, "seed": args.seed,
        "seconds": args.seconds, "trace": args.trace, "out": out, "t0": T0,
        "platform": "tpu",
    }
    spec_path = os.path.join(out, "spec.json")
    harness.write_json(spec_path, spec)
    child = harness.Child(
        [sys.executable, resolved["driver"], "--spec", spec_path],
        harness.child_env(), os.path.join(out, "driver.log"),
    )
    try:
        rc = child.wait(DEADLINE_SECS - (time.time() - T0))
    finally:
        child.kill()
    result_path = os.path.join(out, "result.json")
    if rc != 0 or not os.path.isfile(result_path):
        harness.log(f"driver {'timed out' if rc is None else f'exit {rc}'}; "
                    f"log tail:\n{child.log_tail()}")
        return 1
    with open(result_path) as f:
        result = json.load(f)
    if args.trace:
        metrics = harness.read_per_layer(resolved, result["records"])
    else:
        units = {m["name"]: m["unit"] for m in resolved["end_to_end"]}
        metrics = {k: {"value": float(v), "unit": units[k]}
                   for k, v in result["end_to_end"].items() if k in units}
        missing = set(units) - set(metrics)
        if missing:
            harness.log(f"driver did not report {sorted(missing)}")
            return 1
    for line in result.get("notes", []):
        harness.log(line)
    print(harness.final_line(result, metrics), flush=True)
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
