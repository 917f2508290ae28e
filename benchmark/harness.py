"""What every part of the benchmark shares and that needs no jax: reading
``BENCHMARK.json``, resolving a cell's names to files, running a driver as
a child process tree, loading per-layer metric readers, and writing the
contract's last line.

Adding a configuration, a traffic mix, a driver or a per-layer metric is
adding a file under ``configs/``, ``traffic/``, ``drivers/`` or
``metrics/`` plus an entry in ``BENCHMARK.json``; nothing here names one.
"""

from __future__ import annotations

import importlib.util
import json
import os
import signal
import subprocess
import sys
import time
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
OUT_ROOT = os.path.join(BENCH_DIR, ".out")      # records, logs, traces
CACHE_ROOT = os.path.join(BENCH_DIR, ".cache")  # what outlasts a run


def load_benchmark() -> Dict[str, Any]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _read_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_traffic(name: str) -> Dict[str, Any]:
    """``traffic/<name>.json``; a file with ``base`` holds only what it
    changes in that mix (its ``rehearse`` block likewise)."""
    t = _read_json(os.path.join(BENCH_DIR, "traffic", name + ".json"))
    if "base" in t:
        base = load_traffic(t.pop("base"))
        t["rehearse"] = {**base.get("rehearse", {}), **t.get("rehearse", {})}
        t = {**base, **t}
    return t


def resolve_cell(name: str, bench: Optional[Dict[str, Any]] = None,
                 ) -> Dict[str, Any]:
    """Everything one run needs, found by the names in BENCHMARK.json."""
    bench = bench or load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    (config,) = [c for c in bench["configs"] if c["name"] == cell["config"]]
    traffic = load_traffic(cell["traffic"])
    driver_path = os.path.join(BENCH_DIR, "drivers",
                               traffic["driver"] + ".py")
    if not os.path.isfile(driver_path):
        raise FileNotFoundError(driver_path)

    def here(m: Dict[str, Any]) -> bool:
        return "workloads" not in m or name in m["workloads"]

    return {
        "cell": cell,
        "config_name": config["name"],
        "config": _read_json(os.path.join(ROOT, config["file"])),
        "traffic_name": cell["traffic"],
        "traffic": traffic,
        "driver": driver_path,
        "end_to_end": [m for m in bench["end_to_end"] if here(m)],
        "per_layer": [m for m in bench["per_layer"] if here(m)],
    }


def metric_reader(name: str) -> Callable[[Dict[str, Any]], Optional[float]]:
    """``metrics/<name>.py``'s ``read(records)``: the metric's value from
    the run's records, or None where there is nothing to read."""
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_per_layer(resolved: Dict[str, Any], records: Dict[str, Any],
                   ) -> Dict[str, Dict[str, Any]]:
    """The cell's per-layer metrics; one with nothing to read is left out,
    as is one whose end-to-end metric this cell does not report."""
    e2e = {m["name"] for m in resolved["end_to_end"]}
    out = {}
    for m in resolved["per_layer"]:
        if m["moves"] not in e2e:
            continue
        v = metric_reader(m["name"])(records)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def child_env(cpu: bool = False) -> Dict[str, str]:
    """Environment of a driver process: the repo importable, the TPU's
    compiler logs off /tmp. ``cpu`` pins jax to the CPU (clients, the
    launcher parent); device owners inherit the platform untouched."""
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("TPU_LOG_DIR", "disabled")
    if cpu:
        env["JAX_PLATFORMS"] = "cpu"
    return env


class Child:
    """One child process. ``run.py`` gives a driver a session of its own,
    so that its whole tree can be ended and waited for; a driver's own
    children stay in that session (``own_session=False``), so that they
    go with it even when the driver is killed at a deadline."""

    def __init__(self, cmd: List[str], env: Dict[str, str], log_path: str,
                 own_session: bool = True):
        os.makedirs(os.path.dirname(log_path), exist_ok=True)
        self.log_path = log_path
        self.own_session = own_session
        with open(log_path, "w") as log:
            self.proc = subprocess.Popen(
                cmd, cwd=ROOT, env=env, stdin=subprocess.DEVNULL,
                stdout=log, stderr=subprocess.STDOUT,
                start_new_session=own_session,
            )

    def alive(self) -> bool:
        return self.proc.poll() is None

    def wait(self, timeout: float) -> Optional[int]:
        try:
            return self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            return None

    def kill(self) -> None:
        """SIGKILL the child — its whole session when it has its own — and
        reap it."""
        try:
            if self.own_session:
                os.killpg(self.proc.pid, signal.SIGKILL)
            else:
                self.proc.kill()
        except (ProcessLookupError, PermissionError):
            pass
        self.proc.wait()

    def log_tail(self, n: int = 6000) -> str:
        with open(self.log_path, errors="replace") as f:
            return f.read()[-n:]


def write_json(path: str, obj: Any) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f)
    os.replace(tmp, path)


def final_line(result: Dict[str, Any], metrics: Dict[str, Any]) -> str:
    """The contract's last stdout line — these keys and no others."""
    line = {
        "correct": bool(result["correct"]),
        "attempted": int(result["attempted"]),
        "failed": int(result["failed"]),
        "metrics": metrics,
        "device": result["device"],
    }
    if result.get("breakdown"):
        line["breakdown"] = result["breakdown"]
    return json.dumps(line)


def log(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)
