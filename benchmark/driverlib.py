"""What the drivers' device-owning processes share: the spec, the device
as jax reports it, the profiler window, host spans around calls into a
layer, and the comparison with the plain reference that decides
``correct``. Imports jax.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import trace_reduce  # noqa: E402

# Served / trained logprobs against the float32 reference. The program
# multiplies in bf16 (the trainer by choice, the server because a TPU's
# default f32 matmul is bf16 passes). Measured on the chip (PERF.md
# section 2): 0.036-0.045 nat at the worst token and 0.010-0.011 on
# average, over ~460 tokens. The limits are about twice that; no run at a
# lower precision was made to show that it fails them.
LOGPROB_MAX_ERR = 0.1
LOGPROB_MEAN_ERR = 0.025


def load_spec(argv: Optional[List[str]] = None) -> Dict[str, Any]:
    ap = argparse.ArgumentParser()
    ap.add_argument("--spec", required=True)
    ap.add_argument("--role", default="main")
    args = ap.parse_args(argv)
    with open(args.spec) as f:
        spec = json.load(f)
    spec["role"] = args.role
    spec["spec_path"] = args.spec
    return spec


def build_experiment(spec: Dict[str, Any], name_resolve: bool = False):
    """The experiment config a user's command line would give: the traffic
    file's experiment class with its overrides, files under the run's
    output directory, the mock tokenizer (the machine has no tokenizer
    files). ``actor.path`` is a placeholder that is never read: the
    drivers make the weights from the seed."""
    from areal_tpu.api import cli_args as CA
    from areal_tpu.experiments import common as C
    from areal_tpu.experiments import make_experiment_cls

    t = spec["traffic"]
    exp = CA.apply_overrides(make_experiment_cls(t["experiment"])(), [
        "experiment_name=bench", f"trial_name={spec['workload']}",
        f"cluster.fileroot={spec['out']}/exps", "mock_tokenizer=true",
        f"n_gpus_per_node={spec['cell']['chips']}",
        "actor.path=weights-from-seed", *t["overrides"],
    ])
    CA.validate_config(exp)
    if name_resolve:
        C.setup_name_resolve(exp)
    return exp


def require_device(spec: Dict[str, Any]) -> Dict[str, Any]:
    """The device as jax reports it; raises when it is not the platform
    and chip count the cell asks for — never a fallback."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != spec["platform"]:
        raise SystemExit(f"the cell needs platform {spec['platform']!r}; "
                         f"jax found {dev}")
    need = int(spec["cell"]["chips"])
    if spec["platform"] == "tpu" and dev["count"] < need:
        raise SystemExit(f"the cell needs {need} chip(s); jax found {dev}")
    return dev


def memory_peak_bytes() -> Optional[int]:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def span(name: str):
    """A host span in the profiler's own trace (no cost when no trace is
    being taken beyond a TraceMe check)."""
    import jax

    return jax.profiler.TraceAnnotation(trace_reduce.HOST_SPAN_PREFIX + name)


def wrap_span(obj: Any, attr: str, name: str) -> None:
    """Put a host span around every call of ``obj.attr`` (a plain
    function or bound method) — from the benchmark's side, leaving the
    program's file untouched."""
    fn = getattr(obj, attr)

    @functools.wraps(fn)
    def wrapped(*a, **kw):
        with span(name):
            return fn(*a, **kw)

    setattr(obj, attr, wrapped)


class TraceWindow:
    """Starts and stops the profiler around a short part of the measured
    window and reduces what it wrote. Only the process that holds the chip
    can trace it."""

    def __init__(self, out_dir: str):
        self.dir = os.path.join(out_dir, "trace")
        self.on = False
        self.t_start = 0.0
        self.wall_s = 0.0

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # traces are large; host spans stay
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.on, self.t_start = True, time.monotonic()

    def stop(self) -> None:
        import jax

        if not self.on:
            return
        self.wall_s = time.monotonic() - self.t_start
        jax.profiler.stop_trace()
        self.on = False

    def reduce(self) -> Dict[str, Any]:
        red = trace_reduce.reduce_trace(self.dir)
        if red:
            red["wall_s"] = self.wall_s
        return red


def breakdown(red: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    if not red:
        return None
    return {"device_ops": trace_reduce.top(red["ops"]),
            "idle_gaps": trace_reduce.top(red["idle_gaps"],
                                          merge=lambda k: k)}


def compare_logprobs(got: np.ndarray, ref: np.ndarray) -> Dict[str, Any]:
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    err = np.abs(got - ref)
    return {
        "n": int(err.size), "max_err": float(err.max()),
        "mean_err": float(err.mean()),
        "ok": bool(np.isfinite(got).all() and err.max() <= LOGPROB_MAX_ERR
                   and err.mean() <= LOGPROB_MEAN_ERR),
    }


def reference_logprobs(params, cfg_file: Dict[str, Any], tokens) -> np.ndarray:
    """[T-1] float32 logprobs of tokens[1:] under the plain reference, in
    the process that holds the chip and the weights."""
    import jax

    from benchmark import reference

    with jax.default_matmul_precision("highest"):
        return np.asarray(reference.token_logprobs(params, cfg_file, tokens))


def cache_counts() -> Dict[str, Any]:
    from areal_tpu.base import compile_watch

    return dict(compile_watch.cache_stats() or {})


@contextlib.contextmanager
def timed(store: Dict[str, float], key: str):
    t = time.monotonic()
    try:
        yield
    finally:
        store[key] = store.get(key, 0.0) + time.monotonic() - t
