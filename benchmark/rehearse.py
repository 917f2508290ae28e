"""The rehearsals that cost no chip time (``on-chip-measurement`` guide,
section 2), for every cell, before any chip call:

    python3 benchmark/rehearse.py tiny    [--workload <name>] [--trace 1]
    python3 benchmark/rehearse.py tiny    --traffic async-ppo-d2f2 --chips 4
    python3 benchmark/rehearse.py compile [--config <name> --traffic <name>]

``tiny``     runs a cell's driver end to end here on the CPU at a toy
             size (the traffic file's ``rehearse`` block, a two-layer
             model; cells of 4 chips get 4 virtual CPU devices). Its
             output is labelled ``cpu`` and is never a contract line.
``compile``  compiles the model's forward + backward and the decode step
             at the shapes the cells' traffic files name, for a described
             ``v5e:2x2`` chip, and prints ``memory_analysis()``.

Nothing here is a measurement: no time, rate or share it prints is a
device number.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402


def scaled_shape(shape: dict, scale: float) -> dict:
    """The traffic file's length distributions shrunk by one factor."""
    out = dict(shape)
    for key, v in shape.items():
        if isinstance(v, dict) and "median" in v:
            out[key] = {**v, **{k: max(1, round(v[k] * scale))
                                for k in ("median", "min", "max",
                                          "multiple_of") if k in v}}
    return out


def tiny_spec(workload: str, trace: int, seconds: float,
              unshipped: dict = None) -> dict:
    """``unshipped``: a workloads entry that is not in BENCHMARK.json (a
    cell whose driver and traffic are kept for later, such as the async
    one) — resolved like any other."""
    bench = harness.load_benchmark()
    if unshipped:
        bench["workloads"].append(unshipped)
        if unshipped["config"] not in [c["name"] for c in bench["configs"]]:
            bench["configs"].append({
                "name": unshipped["config"],
                "file": f"benchmark/configs/{unshipped['config']}.json"})
    r = harness.resolve_cell(workload, bench)
    cfg = dict(r["config"])
    cfg.update(num_hidden_layers=2, hidden_size=cfg["num_attention_heads"] * 8,
               intermediate_size=64, vocab_size=512)
    t = dict(r["traffic"])
    reh = dict(t.pop("rehearse", {}))
    t["overrides"] = t.get("overrides", []) + reh.pop("overrides_extra", [])
    scale = reh.pop("length_scale", 1.0)
    if "shape" in t:
        t["shape"] = scaled_shape(t["shape"], scale)
    t.update(reh)
    out = os.path.join(harness.OUT_ROOT, "rehearse-" + workload)
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    return {**r, "config": cfg, "traffic": t, "workload": workload,
            "seed": 1, "seconds": seconds, "trace": trace, "out": out,
            "t0": time.time(), "platform": "cpu"}


def run_tiny(args) -> int:
    bench = harness.load_benchmark()
    names = [args.workload] if args.workload else [
        w["name"] for w in bench["workloads"]]
    unshipped = None
    if args.traffic:  # e.g. --traffic async-ppo-d2f2 --chips 4
        unshipped = {"name": f"{args.config}.{args.traffic}",
                     "config": args.config, "traffic": args.traffic,
                     "chips": args.chips, "why": "rehearsal only"}
        names = [unshipped["name"]]
    rc = 0
    for name in names:
        spec = tiny_spec(name, args.trace, args.seconds, unshipped)
        path = os.path.join(spec["out"], "spec.json")
        harness.write_json(path, spec)
        env = harness.child_env(cpu=True)
        n = int(spec["cell"]["chips"])
        if n > 1:
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                                f" --xla_force_host_platform_device_count={n}")
        child = harness.Child([sys.executable, spec["driver"], "--spec", path],
                              env, os.path.join(spec["out"], "driver.log"))
        try:
            code = child.wait(1500)
        finally:
            child.kill()
        res_path = os.path.join(spec["out"], "result.json")
        if code != 0 or not os.path.isfile(res_path):
            print(f"cpu rehearsal {name}: FAILED (exit {code})\n"
                  f"{child.log_tail(4000)}")
            rc = 1
            continue
        with open(res_path) as f:
            res = json.load(f)
        per_layer = {}
        readers = ([m["name"] for m in spec["per_layer"]] if not unshipped
                   else sorted(f[:-3] for f in os.listdir(os.path.join(
                       harness.BENCH_DIR, "metrics")) if f.endswith(".py")))
        for m in readers:
            try:  # a metric against the chip's peaks has none on the CPU
                v = harness.metric_reader(m)(res["records"])
            except KeyError as e:
                v = f"needs the chip ({e})"
            if v is not None or not unshipped:
                per_layer[m] = v
        print(json.dumps({
            "cpu_rehearsal": name, "correct": res["correct"],
            "attempted": res["attempted"], "failed": res["failed"],
            "end_to_end_keys": sorted(res["end_to_end"]),
            "per_layer_read_cpu": per_layer, "device": res["device"],
            "notes": res.get("notes"),
        }))
        rc = rc or (0 if res["correct"] else 1)
    return rc


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="what", required=True)
    t = sub.add_parser("tiny")
    t.add_argument("--workload")
    t.add_argument("--trace", type=int, default=0)
    t.add_argument("--seconds", type=float, default=8.0)
    t.add_argument("--traffic", help="a traffic file no shipped cell uses")
    t.add_argument("--config", default="qwen2.5-0.5b")
    t.add_argument("--chips", type=int, choices=(1, 4), default=4)
    c = sub.add_parser("compile")
    c.add_argument("--config", help="with --traffic: a cell not shipped")
    c.add_argument("--traffic")
    args = ap.parse_args()
    if args.what == "tiny":
        return run_tiny(args)
    from benchmark import rehearse_compile

    pairs = ([(args.config, args.traffic)] if args.traffic else
             [(w["config"], w["traffic"])
              for w in harness.load_benchmark()["workloads"]])
    return rehearse_compile.main(pairs)


if __name__ == "__main__":
    sys.exit(main())
