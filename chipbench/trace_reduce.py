"""Reduce a profiler trace (``*.xplane.pb``) to the benchmark's numbers.

* busy: the union of the intervals in which an operation ran on a device
  (the ``XLA Ops`` line of a ``/device:TPU:<n>`` plane that has one),
  inside the window, averaged over the devices;
* programs: device seconds and dispatches per compiled program (the
  ``XLA Modules`` line; ``jit__head_fn(12)`` counts as ``jit__head_fn``);
* device_ops: device seconds per operation name;
* idle gaps: the stretches of the window in which no device operation ran,
  each named by the innermost host span (``chipbench.*``) open at its
  middle, or ``"none"``.

The window is the host span ``chipbench.window``.  ``load`` reads a trace
file; ``reduce`` works on plain ``(name, start_ns, end_ns)`` tuples, so it
can be checked by hand.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, float, float]        # (name, start_ns, end_ns)

WINDOW = "chipbench.window"
SPAN_PREFIX = "chipbench."
_DEVICE = re.compile(r"^/device:TPU:\d+")
_SUFFIX = re.compile(r"\(\d+\)$")


def find_trace(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str, device_pattern=_DEVICE) -> Tuple[
        Dict[str, Dict[str, List[Event]]], List[Event]]:
    """({device plane: {"ops": [...], "modules": [...]}}, host spans)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List[Event]]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        if device_pattern.match(plane.name):
            lines = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    lines[key] += [(e.name, e.start_ns, e.end_ns)
                                   for e in line.events]
            if lines["ops"]:
                devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.end_ns) for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    return devices, spans


def _union(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def _innermost(inner: List[Event]):
    """A function from a time to the innermost span open then.

    Spans of one thread nest, so the innermost span open at ``t`` is the
    latest-starting span before ``t`` or one of its ancestors."""
    starts = [s for _, s, _ in inner]
    parent, stack = [], []
    for i, (_, s, e) in enumerate(inner):
        while stack and inner[stack[-1]][2] <= s:
            stack.pop()
        parent.append(stack[-1] if stack else -1)
        stack.append(i)

    def at(t: float) -> str:
        i = bisect.bisect_right(starts, t) - 1
        while i >= 0 and inner[i][2] <= t:
            i = parent[i]
        return inner[i][0] if i >= 0 else "none"
    return at


def reduce(devices: Dict[str, Dict[str, List[Event]]], spans: List[Event],
           top: int = 10) -> dict:
    windows = [(s, e) for n, s, e in spans if n == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW} span in the trace")
    lo, hi = windows[-1]
    window_s = (hi - lo) * 1e-9
    inner = sorted((e for e in spans if e[0] != WINDOW),
                   key=lambda e: (e[1], -e[2]))
    span_at = _innermost(inner)
    busy, programs, ops, gaps = [], collections.defaultdict(float), \
        collections.defaultdict(float), []
    n_calls = collections.Counter()
    for lines in devices.values():
        op_ev = _clip(lines["ops"], lo, hi)
        for n, s, e in _clip(lines["modules"], lo, hi):
            name = _SUFFIX.sub("", n)
            programs[name] += (e - s) * 1e-9
            n_calls[name] += 1
        for n, s, e in op_ev:
            ops[n] += (e - s) * 1e-9
        merged = _union([(s, e) for _, s, e in op_ev])
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((span_at(0.5 * (s + e)), (e - s) * 1e-9))
    if not devices:
        raise ValueError("no device plane in the trace")
    gaps.sort(key=lambda g: -g[1])
    span_s = collections.defaultdict(float)
    for n, s, e in _clip(inner, lo, hi):
        span_s[n] += (e - s) * 1e-9
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / len(busy),
        "programs": {n: {"s": programs[n], "calls": n_calls[n]}
                     for n in programs},
        "device_ops": sorted(([n, s] for n, s in ops.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": [[n, s] for n, s in gaps[:top]],
        "host_spans_s": dict(span_s),
    }
