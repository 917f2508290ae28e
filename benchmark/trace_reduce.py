"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read: per chip the busy time (union of the intervals in
which an operation ran on the device), per operation its self time, and
each idle gap attributed to what the host was doing in it.

Reads the file with ``jax.profiler.ProfileData`` and nothing else. Device
planes are those named ``/device:TPU:<n>``; on each, the line ``XLA Ops``
holds one event per executed HLO operation, named by the instruction's
whole text; it is cut to ``<name> <first shape>`` (two programs reuse a
name such as ``flash_attention.6`` for different shapes). Parents such as
``while`` contain their children, hence self time. Host spans are the
``jax.profiler.TraceAnnotation`` events whose names start with ``bench/``,
on whichever host-thread line they were recorded.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_SPAN_PREFIX = "bench/"


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _self_times(events: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Self time per name of properly nested events (start, end, name)."""
    out: Dict[str, float] = {}
    stack: List[List[Any]] = []  # [end, name, child_time, start]

    def close(top):
        end, name, child, start = top
        out[name] = out.get(name, 0.0) + max((end - start) - child, 0.0)
        if stack:
            stack[-1][2] += end - start

    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        stack.append([e, name, 0.0, s])
    while stack:
        close(stack.pop())
    return out


SHAPE = re.compile(r"\w+\[[\d,]*\]")


def op_key(text: str) -> str:
    """An ``XLA Ops`` event's text → ``<name> <first shape>``."""
    name, _, rest = text.partition(" = ")
    m = SHAPE.search(rest)
    return name.lstrip("%") + (" " + m.group(0) if m else "")


def base_name(op: str) -> str:
    """``fusion.123 bf16[8,128]`` → ``fusion``: instances of one kind of
    op add up."""
    return re.sub(r"\.\d+$", "", op.split(" ")[0]) or op


def reduce_planes(planes: List[Dict[str, Any]], t_lo: Optional[float] = None,
                  t_hi: Optional[float] = None) -> Dict[str, Any]:
    """The reduction proper, over plain data so that it can be tested
    without a trace file. ``planes``: [{"name", "lines": [{"name",
    "events": [(start_s, end_s, name)]}]}]. The window is [t_lo, t_hi], by
    default from the first to the last device event."""
    device_events: Dict[int, List[Tuple[float, float, str]]] = {}
    host_spans: List[Tuple[float, float, str]] = []
    for pl in planes:
        m = DEVICE_PLANE.match(pl["name"])
        for ln in pl["lines"]:
            if m and ln["name"] == OPS_LINE:
                device_events.setdefault(int(m.group(1)), []).extend(
                    ln["events"])
            elif not m:
                host_spans += [ev for ev in ln["events"]
                               if ev[2].startswith(HOST_SPAN_PREFIX)]
    if not device_events:
        return {}
    all_ev = [ev for evs in device_events.values() for ev in evs]
    lo = min(s for s, _, _ in all_ev) if t_lo is None else t_lo
    hi = max(e for _, e, _ in all_ev) if t_hi is None else t_hi
    window = max(hi - lo, 1e-12)
    busy: Dict[int, float] = {}
    ops: Dict[str, float] = {}
    op_calls: Dict[str, int] = {}
    gaps: Dict[str, float] = {}
    # innermost host span at a time: the one that started last
    host_spans.sort()
    for chip, evs in sorted(device_events.items()):
        evs = [(max(s, lo), min(e, hi), n) for s, e, n in evs
               if e > lo and s < hi]
        merged = _union([(s, e) for s, e, _ in evs])
        busy[chip] = sum(e - s for s, e in merged)
        for name, t in _self_times(evs).items():
            ops[name] = ops.get(name, 0.0) + t / len(device_events)
        for _, _, n in evs:
            op_calls[n] = op_calls.get(n, 0) + 1
        edges = [lo] + [x for se in merged for x in se] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 - g0 <= 0:
                continue
            mid = (g0 + g1) / 2
            cover = [n for s, e, n in host_spans if s <= mid < e]
            name = cover[-1] if cover else "unattributed"
            gaps[name] = gaps.get(name, 0.0) + (g1 - g0) / len(device_events)
    return {
        "window_s": window,
        "busy_s_per_chip": [busy[c] for c in sorted(busy)],
        "busy_s": sum(busy.values()) / len(busy),
        "ops": ops,            # self seconds per op instance name, per chip
        "op_calls": op_calls,
        "idle_gaps": gaps,     # idle seconds per host span, per chip
    }


def read_xplane(path: str, keep_stats: Tuple[str, ...] = ()
                ) -> List[Dict[str, Any]]:
    """``.xplane.pb`` → the plain planes ``reduce_planes`` takes (seconds)."""
    from jax.profiler import ProfileData

    planes = []
    for pl in ProfileData.from_file(path).planes:
        is_dev = bool(DEVICE_PLANE.match(pl.name))
        lines = []
        for ln in pl.lines:
            if is_dev and ln.name != OPS_LINE:
                continue
            evs = []
            for ev in ln.events:
                if not is_dev and not ev.name.startswith(HOST_SPAN_PREFIX):
                    continue
                evs.append((ev.start_ns * 1e-9,
                            (ev.start_ns + ev.duration_ns) * 1e-9,
                            op_key(ev.name) if is_dev else ev.name))
            if evs:
                lines.append({"name": ln.name, "events": evs})
        if lines:
            planes.append({"name": pl.name, "lines": lines})
    return planes


def reduce_trace(trace_dir: str) -> Dict[str, Any]:
    path = find_xplane(trace_dir)
    if path is None:
        return {}
    return reduce_planes(read_xplane(path))


def top(d: Dict[str, float], n: int = 10, merge=base_name,
        ) -> List[List[Any]]:
    """The ``n`` largest entries as [[name, seconds], ...], instances of
    one kind of op merged."""
    agg: Dict[str, float] = {}
    for k, v in d.items():
        agg[merge(k)] = agg.get(merge(k), 0.0) + v
    return [[k, v] for k, v in sorted(agg.items(), key=lambda kv: -kv[1])[:n]]

