"""Reduce a JAX profiler trace to the device's busy time, its top operations
and its idle gaps, labelled by what the host was doing.

Reads the ``.xplane.pb`` that ``jax.profiler.start_trace`` writes, with
``jax.profiler.ProfileData`` alone.  The traced window is the host span
``bench.traced_window`` that the harness opens just after the trace starts
and closes just before it stops.  Device planes are those named
``/device:<kind>:<n>``; an operation is an event of their ``XLA Ops`` line,
a program an event of their ``XLA Modules`` line.  Busy time is the union
of the operations' intervals inside the window, averaged over the devices
that ran any; an idle gap is a stretch of the window with no operation on
a device, labelled by the innermost ``bench.*`` host span open at its
middle (``host outside the store`` where none is).
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.traced_window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NO_SPAN = "host outside the store"

Interval = Tuple[float, float, str]   # start ns, end ns, name


def newest_xplane(trace_dir: str) -> Optional[str]:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def load(path: str):
    import jax

    return jax.profiler.ProfileData.from_file(path)


def _events(line) -> List[Interval]:
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events]


def device_lines(pd, line_name: str) -> Dict[str, List[Interval]]:
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name == line_name:
                evs = _events(line)
                if evs:
                    out[plane.name] = evs
    return out


def host_spans(pd) -> List[Interval]:
    out: List[Interval] = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend(ev for ev in _events(line) if ev[2].startswith(SPAN_PREFIX))
    return out


def merge(intervals: List[Interval], lo: float, hi: float) -> List[Tuple[float, float]]:
    """Union of the intervals, clipped to [lo, hi], in order."""
    out: List[List[float]] = []
    for s, e, _ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def label_points(spans: List[Interval], points: List[float]) -> List[str]:
    """For each time in `points`, the innermost span open at it (spans of
    one thread nest, so that is the latest-starting one still open)."""
    spans = sorted(spans, key=lambda sp: (sp[0], -sp[1]))
    out = [NO_SPAN] * len(points)
    stack: List[Interval] = []
    i = 0
    for j in sorted(range(len(points)), key=points.__getitem__):
        t = points[j]
        while i < len(spans) and spans[i][0] <= t:
            while stack and stack[-1][1] <= spans[i][0]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][1] <= t:
            stack.pop()
        if stack and stack[-1][2] != WINDOW_SPAN:
            out[j] = stack[-1][2][len(SPAN_PREFIX):]
    return out


def reduce(pd, top: int = 10) -> Optional[Dict]:
    """busy_s, window_s, device_ops and idle_gaps of the traced window, or
    None where the trace holds no window span or no device operation."""
    spans = host_spans(pd)
    windows = [sp for sp in spans if sp[2] == WINDOW_SPAN]
    ops = device_lines(pd, OPS_LINE)
    if not windows or not ops:
        return None
    lo, hi = windows[0][0], windows[0][1]
    busy_by_dev = {dev: merge(evs, lo, hi) for dev, evs in ops.items()}
    busy_by_dev = {d: b for d, b in busy_by_dev.items() if b}
    if not busy_by_dev:
        return None
    busy_ns = sum(sum(e - s for s, e in b) for b in busy_by_dev.values()) / len(busy_by_dev)

    per_program: Dict[str, float] = defaultdict(float)
    modules = device_lines(pd, MODULES_LINE) or ops
    for evs in modules.values():
        for s, e, name in evs:
            clipped = min(e, hi) - max(s, lo)
            if clipped > 0:
                per_program[name] += clipped / 1e9

    idle: Dict[str, float] = defaultdict(float)
    for busy in busy_by_dev.values():
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
        labels = label_points(spans, [(s + e) / 2 for s, e in gaps])
        for (s, e), label in zip(gaps, labels):
            idle[label] += (e - s) / 1e9 / len(busy_by_dev)

    def ranked(d: Dict[str, float]) -> List[List]:
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"busy_s": busy_ns / 1e9, "window_s": (hi - lo) / 1e9,
            "devices": len(busy_by_dev),
            "device_ops": ranked(per_program), "idle_gaps": ranked(idle)}
