"""Reduce a profiler trace (``.xplane.pb``) to busy time, top ops and idle gaps.

* Device planes are ``/device:TPU:<k>``; their operations are the events on
  the line named ``XLA Ops``.  Busy time is the union of those intervals
  inside the traced window, averaged over the chips; idle share is one
  minus busy over the window.
* The window is the host span ``chipbench.window`` that the harness opens
  around what it traces.
* Each idle gap is put down to the harness span (``chipbench.*``, other
  than the window) that overlaps it most, where that span covers at least
  half of the gap: what the host was doing.  Any other gap happened inside
  the program's own threads, which carry no spans of their own yet:
  ``program``.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "chipbench.window"
SPAN_PREFIX = "chipbench."


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def _union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def _overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def read_events(path: str):
    """``(device_ops, host_spans)``: per chip a list of ``(name, start,
    end)`` in ns, and the harness's host spans as ``(name, start, end)``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Tuple[str, float, float]]] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events
                    )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events
                    if e.name.startswith(SPAN_PREFIX)
                )
    return devices, spans


def reduce(path: str, top: int = 10) -> Optional[dict]:
    """Busy and window seconds, top device ops and idle gaps; None when the
    trace holds no window span or no device operation."""
    devices, spans = read_events(path)
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows or not any(devices.values()):
        return None
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    inner = sorted(((n, s, e) for n, s, e in spans if n != WINDOW_SPAN), key=lambda x: x[1])
    busy, op_time = [], defaultdict(float)
    gap_time = defaultdict(float)
    for ops in devices.values():
        if not ops:
            continue
        for name, s, e in ops:
            op_time[name] += _overlap((s, e), (lo, hi))
        covered = _clip(_union([(s, e) for _, s, e in ops]), lo, hi)
        busy.append(sum(e - s for s, e in covered))
        edges = [lo] + [x for iv in covered for x in iv] + [hi]
        pending, active = iter(inner), []
        nxt = next(pending, None)
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge <= gs:
                continue
            while nxt is not None and nxt[1] < ge:
                active.append(nxt)
                nxt = next(pending, None)
            active = [sp for sp in active if sp[2] > gs]
            best, label = 0.5 * (ge - gs), "program"
            for name, s, e in active:
                ov = _overlap((gs, ge), (s, e))
                if ov >= best:
                    best, label = ov, name[len(SPAN_PREFIX):]
            gap_time[label] += (ge - gs) / len(devices)
    by_time = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]  # noqa: E731
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[n, t / 1e9 / len(busy)] for n, t in by_time(op_time)],
        "idle_gaps": [[n, t / 1e9] for n, t in by_time(gap_time)],
    }
