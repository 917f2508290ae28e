"""The traced run seen through the program's own names: device time per
jitted **program** (the ``XLA Modules`` line), device self time per
**scope** (the ``jax.named_scope`` names the program puts on its ops), and
idle time per innermost ``areal/`` host span (``telemetry.span``), with the
counts those spans carry as attributes.

A per-layer reader gets only the run's ``records``, which hold none of
this, so this module reads the run's trace file itself: the newest
``*.xplane.pb`` under ``benchmark/.out/*/trace/`` — in a ``--trace 1`` run
the file the run just wrote. It is read once per process.

Where the names come from. ``ProfileData`` gives each op event the
instruction's text without its metadata, so the op's framework name
(``jit(train_grad)/transpose(jvp(mlp))/dot_general``) is taken from
xprof's ``hlo_stats`` table of the same file (``program_id``,
``hlo_op_name`` → ``tf_op_name``); an op event belongs to the program of
the ``XLA Modules`` event it falls into, whose name ends in that
``program_id``. The scope names are matched as strings: nothing is
imported from the program. A program that has none of the names (the
parent of the PR that added them) gives None everywhere, and the metrics
leave the line.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:  # run as a script
    sys.path.insert(0, ROOT)

from benchmark import harness, readers  # noqa: E402
from benchmark.trace_reduce import (  # noqa: E402
    DEVICE_PLANE, OPS_LINE, _union, base_name)

MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "areal/"
# jax.named_scope names of the program (areal_tpu/base/telemetry.py lists
# them as DEVICE_SCOPES; kept equal by tests/test_program_trace.py).
SCOPES = (
    "embed", "attn_norm", "qkv_proj", "rope", "attention", "o_proj",
    "mlp_norm", "mlp", "moe", "layer_scan", "final_norm", "head", "xent",
    "param_cast", "ppo_loss", "grad_accum", "grad_clip", "adam",
    "param_update", "gae",
)
FLASH = "flash"        # the Pallas kernels' own ops, whatever their scope
UNSCOPED = "unscoped"  # ops that carry no name of the list
MODULE_NAME = re.compile(r"^(.*)\((\d+)\)$")  # jit_train_grad(1154...)
WRAPPER = re.compile(r"^\w+\((.*)\)$")        # transpose(jvp(mlp)) → mlp


def scope_of(framework_name: str) -> Optional[str]:
    """The innermost name of ``SCOPES`` in an op's framework name, with
    the ``jit(..)``, ``jvp(..)``, ``transpose(..)`` wrappers taken off each
    path component; None where there is none."""
    first = framework_name.split(";")[0].split(":")[0]
    for part in reversed(first.split("/")):
        while True:
            m = WRAPPER.match(part)
            if not m:
                break
            part = m.group(1)
        if part in SCOPES:
            return part
    return None


def program_of(module_event: str) -> Tuple[str, str]:
    """``jit_train_grad(1154)`` → (``train_grad``, ``1154``)."""
    m = MODULE_NAME.match(module_event)
    name, pid = (m.group(1), m.group(2)) if m else (module_event, "")
    return (name[4:] if name.startswith("jit_") else name), pid


def op_name(text: str) -> str:
    """An ``XLA Ops`` event's text → the instruction's name."""
    return text.partition(" = ")[0].lstrip("%")


def is_flash(name: str) -> bool:
    key = name + " "
    return bool(readers.FLASH_FWD.search(key) or readers.FLASH_BWD.search(key))


def _event_self_times(events: List[Tuple[float, float, Any]],
                      ) -> List[Tuple[float, Any]]:
    """(self seconds, payload) of properly nested (start, end, payload)
    events: a parent such as ``while`` holds its children."""
    out: List[Tuple[float, Any]] = []
    stack: List[List[Any]] = []  # [end, payload, child_time, start]

    def close(top):
        end, payload, child, start = top
        out.append((max((end - start) - child, 0.0), payload))
        if stack:
            stack[-1][2] += end - start

    for s, e, payload in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        stack.append([e, payload, 0.0, s])
    while stack:
        close(stack.pop())
    return out


def _innermost_timeline(spans: List[Tuple[float, float, str]],
                        ) -> Tuple[List[float], List[Optional[str]]]:
    """Boundaries t[0] < t[1] < ... and, for each [t[i], t[i+1]), the span
    that covers it and started last (the innermost), or None."""
    spans = sorted(spans)
    edges = sorted({x for s, e, _ in spans for x in (s, e)})
    names: List[Optional[str]] = []
    for lo, hi in zip(edges, edges[1:]):
        mid = (lo + hi) / 2
        cover = [n for s, e, n in spans if s <= mid < e]
        names.append(cover[-1] if cover else None)
    return edges, names


def reduce_planes(planes: List[Dict[str, Any]],
                  framework_names: Optional[Dict[Tuple[str, str], str]],
                  ) -> Dict[str, Any]:
    """The reduction proper, over plain data. ``planes`` as
    ``trace_reduce.reduce_planes`` takes them, device planes holding the
    ``XLA Modules`` line too and op events named by instruction name;
    ``framework_names``: (program id, instruction name) → framework name,
    or None where the table could not be read (then no scope is known).
    All seconds are per chip (the mean over the device planes)."""
    device: Dict[int, Dict[str, list]] = {}
    spans: List[Tuple[float, float, str]] = []
    for pl in planes:
        m = DEVICE_PLANE.match(pl["name"])
        for ln in pl["lines"]:
            if m and ln["name"] in (OPS_LINE, MODULES_LINE):
                device.setdefault(int(m.group(1)), {}).setdefault(
                    ln["name"], []).extend(ln["events"])
            elif not m:
                spans += [ev for ev in ln["events"]
                          if ev[2].startswith(SPAN_PREFIX)]
    chips = {c: d for c, d in device.items() if d.get(OPS_LINE)}
    if not chips:
        return {}
    n = len(chips)
    all_ops = [ev for d in chips.values() for ev in d[OPS_LINE]]
    lo = min(s for s, _, _ in all_ops)
    hi = max(e for _, e, _ in all_ops)
    programs: Dict[str, float] = {}
    scopes: Dict[str, float] = {}
    scope_ops: Dict[str, Dict[str, float]] = {}
    idle: Dict[str, float] = {}
    busy = 0.0
    edges, innermost = _innermost_timeline(spans)
    for chip, d in sorted(chips.items()):
        modules = sorted(d.get(MODULES_LINE, []))
        starts = [s for s, _, _ in modules]
        for secs, (s, name) in _event_self_times(
                [(s, e, (s, nm)) for s, e, nm in d[OPS_LINE]]):
            i = bisect.bisect_right(starts, s) - 1
            inside = i >= 0 and s < modules[i][1]
            prog, pid = program_of(modules[i][2]) if inside else ("", "")
            programs[prog] = programs.get(prog, 0.0) + secs / n
            if is_flash(name):
                key = FLASH
            elif framework_names is None:
                continue
            else:
                key = scope_of(framework_names.get((pid, name), "")) \
                    or UNSCOPED
            scopes[key] = scopes.get(key, 0.0) + secs / n
            by_op = scope_ops.setdefault(key, {})
            by_op[base_name(name)] = by_op.get(base_name(name), 0.0) + secs / n
        merged = _union([(s, e) for s, e, _ in d[OPS_LINE]])
        busy += sum(e - s for s, e in merged) / n
        bounds = [lo] + [x for se in merged for x in se] + [hi]
        for g0, g1 in zip(bounds[0::2], bounds[1::2]):
            if g1 - g0 <= 0:
                continue
            i = bisect.bisect_right(edges, (g0 + g1) / 2) - 1
            name = innermost[i] if 0 <= i < len(innermost) else None
            key = name or "unspanned"
            idle[key] = idle.get(key, 0.0) + (g1 - g0) / n
    known = framework_names is not None and any(
        k not in (FLASH, UNSCOPED) for k in scopes)
    return {
        "window_s": max(hi - lo, 1e-12),
        "busy_s": busy,
        "programs": programs,                # busy seconds per jit program
        "scopes": scopes if known else None,  # self seconds per scope
        "scope_ops": scope_ops if known else None,  # ... and kind of op
        "idle": idle if spans else None,      # idle seconds per areal/ span
        "idle_s": sum(idle.values()),
    }


def span_counts(span_stats: List[Tuple[str, Dict[str, Any]]],
                ) -> Dict[str, Dict[str, float]]:
    """Sums of the numeric attributes of the ``areal/`` spans, by span."""
    out: Dict[str, Dict[str, float]] = {}
    for name, stats in span_stats:
        acc = out.setdefault(name, {"n": 0})
        acc["n"] += 1
        for k, v in stats.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                acc[k] = acc.get(k, 0) + v
    return out


def read_xplane(path: str) -> Tuple[List[Dict[str, Any]],
                                    List[Tuple[str, Dict[str, Any]]]]:
    """``.xplane.pb`` → (planes for ``reduce_planes``, [(span, stats)] of
    the ``areal/`` spans)."""
    from jax.profiler import ProfileData

    planes, span_stats = [], []
    for pl in ProfileData.from_file(path).planes:
        is_dev = bool(DEVICE_PLANE.match(pl.name))
        lines = []
        for ln in pl.lines:
            if is_dev and ln.name not in (OPS_LINE, MODULES_LINE):
                continue
            evs = []
            for ev in ln.events:
                name = ev.name
                if not is_dev:
                    if not name.startswith(SPAN_PREFIX):
                        continue
                    span_stats.append((name, dict(ev.stats)))
                elif ln.name == OPS_LINE:
                    name = op_name(name)
                evs.append((ev.start_ns * 1e-9,
                            (ev.start_ns + ev.duration_ns) * 1e-9, name))
            if evs:
                lines.append({"name": ln.name, "events": evs})
        if lines:
            planes.append({"name": pl.name, "lines": lines})
    return planes, span_stats


def read_framework_names(path: str) -> Optional[Dict[Tuple[str, str], str]]:
    """(program id, instruction name) → framework name, from xprof's
    ``hlo_stats`` of the trace file; None where xprof cannot give it.
    xprof leaves an ``*.op_stats.pb`` beside the file it reads."""
    try:
        from xprof.convert import raw_to_tool_data

        data, _ = raw_to_tool_data.xspace_to_tool_data(
            [path], "hlo_stats", {})
        table = json.loads(data)
        col = {c["id"]: i for i, c in enumerate(table["cols"])}
        out = {}
        for row in table["rows"]:
            c = row["c"]
            out[(str(c[col["program_id"]]["v"]),
                 str(c[col["hlo_op_name"]]["v"]))] = str(
                     c[col["tf_op_name"]]["v"])
        return out
    except Exception as e:  # noqa: BLE001 — a metric is left out, no more
        harness.log(f"program_trace: no framework names from xprof: {e!r}")
        return None


def reduce_file(path: str) -> Dict[str, Any]:
    planes, span_stats = read_xplane(path)
    red = reduce_planes(planes, read_framework_names(path))
    if red:
        red["counts"] = span_counts(span_stats)
        red["path"] = path
    return red


def newest_trace() -> Optional[str]:
    files = glob.glob(os.path.join(
        harness.OUT_ROOT, "*", "trace", "plugins", "profile", "*",
        "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


_LOADED: Dict[str, Dict[str, Any]] = {}


def load(records: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The reduction of the run's trace, or None in an untraced run."""
    if not records.get("trace"):
        return None
    path = newest_trace()
    if path is None:
        return None
    if path not in _LOADED:
        _LOADED[path] = reduce_file(path)
    return _LOADED[path] or None


# ---- what the metric files under metrics/ call ----

def program_busy_pct(records, *programs: str) -> Optional[float]:
    red = load(records)
    if not red or not any(p in red["programs"] for p in programs):
        return None
    return 100.0 * sum(red["programs"].get(p, 0.0)
                       for p in programs) / red["busy_s"]


def scope_busy_pct(records, *scopes: str) -> Optional[float]:
    red = load(records)
    if not red or red["scopes"] is None:
        return None
    return 100.0 * sum(red["scopes"].get(s, 0.0)
                       for s in scopes) / red["busy_s"]


def span_idle_pct(records, *suffixes: str) -> Optional[float]:
    """Idle seconds under the ``areal/`` spans whose names end in one of
    ``suffixes``, over the traced window."""
    red = load(records)
    if not red or red["idle"] is None:
        return None
    return 100.0 * sum(v for k, v in red["idle"].items()
                       if k.endswith(suffixes)) / red["window_s"]


def unspanned_idle_pct(records) -> Optional[float]:
    red = load(records)
    if not red or red["idle"] is None or not red["idle_s"]:
        return None
    return 100.0 * red["idle"].get("unspanned", 0.0) / red["idle_s"]


def span_fill_pct(records, span: str) -> Optional[float]:
    """``real_tokens`` over ``padded_tokens`` summed over one span's
    events."""
    red = load(records)
    c = (red or {}).get("counts", {}).get(SPAN_PREFIX + span)
    if not c or not c.get("padded_tokens"):
        return None
    return 100.0 * c["real_tokens"] / c["padded_tokens"]


if __name__ == "__main__":
    # python3 benchmark/program_trace.py [file.xplane.pb]: the whole
    # reduction of a trace (default: the newest run's) as JSON.
    print(json.dumps(reduce_file(
        sys.argv[1] if len(sys.argv) > 1 else newest_trace()), indent=1))
