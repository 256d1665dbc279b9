"""Reduce a profiler trace as ``tracecut`` does, naming idle gaps after the
program's own spans.

The program's Telemetry, at level ``trace``, writes each span it records
into the profiler's trace as a host event ``repro.<kind>``
(``repro.engine.fetch``, ``repro.serve.round``), on the clock of the
device's ``XLA Ops``.  ``reduce`` reads those beside the harness's spans:

* an idle gap that a program span overlaps is cut at the edges of the
  spans in it (the harness's too), and each piece goes to what a thread
  was doing there: on each thread, the innermost program span covering
  the piece.  A thread waiting for the engine lock (``*.lock_wait``)
  gives way to one at work, since the thread that waits is not what
  holds the device idle, the one it waits for is; of the rest the most
  deeply nested span wins, then the one that started last.  A piece no
  program span covers goes to the harness span covering it, else to
  ``program``.  One gap between two jobs thus splits into the result's
  fetch, the driver's own work and the next upload;
* a gap no program span overlaps keeps ``tracecut``'s label: the harness
  span covering half of it, else ``program``;
* ``spans``: for each program span name in the window (``repro.``
  dropped), ``count`` (its spans that overlap the window), ``seconds``
  (the union of its spans inside the window) and ``busy_s`` (device-busy
  seconds inside that union, averaged over the chips).

A trace with no ``repro.*`` event reduces to exactly what
``tracecut.reduce`` gives, with no ``spans`` key.
"""
from __future__ import annotations

import itertools
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from chipbench.tracecut import (
    DEVICE_PLANE,
    OPS_LINE,
    SPAN_PREFIX,
    WINDOW_SPAN,
    Interval,
    _clip,
    _overlap,
    _union,
)

PROGRAM_PREFIX = "repro."
WAIT_SUFFIX = "lock_wait"

Event = Tuple[str, float, float]
ProgramSpan = Tuple[str, float, float, int, int]  # name, start, end, depth, thread


def nest(spans: List[Event], thread: int = 0) -> List[ProgramSpan]:
    """One thread's spans with their depth (how many of the others hold
    each one) and the thread's number."""
    out: List[ProgramSpan] = []
    ends: List[float] = []
    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while ends and ends[-1] <= s:
            ends.pop()
        out.append((name, s, e, len(ends), thread))
        ends.append(e)
    return out


def read_events(path: str):
    """``(device_ops, harness_spans, program_spans)``: per chip a list of
    ``(name, start, end)`` in ns, the harness's ``chipbench.*`` spans, and
    the program's ``repro.*`` spans as ``(name, start, end, depth,
    thread)``, a thread being a line of a host plane."""
    from jax.profiler import ProfileData

    devices: Dict[str, List[Event]] = {}
    harness: List[Event] = []
    program: List[ProgramSpan] = []
    lines = itertools.count()
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events
                    )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                thread: List[Event] = []
                for e in line.events:
                    ev = (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    if e.name.startswith(SPAN_PREFIX):
                        harness.append(ev)
                    elif e.name.startswith(PROGRAM_PREFIX):
                        thread.append(ev)
                program.extend(nest(thread, next(lines)))
    return devices, harness, program


def reduce(path: str, top: int = 10) -> Optional[dict]:
    return reduce_events(*read_events(path), top=top)


def _harness_label(gap: Interval, active: List[Event]) -> str:
    best, label = 0.5 * (gap[1] - gap[0]), "program"
    for name, s, e in active:
        ov = _overlap(gap, (s, e))
        if ov >= best:
            best, label = ov, name[len(SPAN_PREFIX):]
    return label


def _program_label(covering: List[ProgramSpan]) -> Optional[str]:
    inner = {}
    for name, s, _, depth, thread in covering:
        if depth >= inner.get(thread, (-1,))[0]:
            inner[thread] = (depth, s, name)
    work = [v for v in inner.values() if not v[2].endswith(WAIT_SUFFIX)]
    pick = max(work or inner.values(), default=None)
    return None if pick is None else pick[2][len(PROGRAM_PREFIX):]


def _pieces(gap: Interval, program: List[ProgramSpan], harness: List[Event]):
    """The gap cut at the edges of the spans in it: ``(start, end, label)``."""
    edges = (x for sp in program + harness for x in sp[1:3] if gap[0] < x < gap[1])
    cuts = sorted({*gap, *edges})
    for a, b in zip(cuts, cuts[1:]):
        label = _program_label([sp for sp in program if sp[1] <= a and sp[2] >= b])
        yield a, b, label or _harness_label((a, b), harness)


def _sweep(spans, gaps):
    """For each gap (in order), the spans (sorted by start) that overlap it."""
    pending, active = iter(spans), []
    nxt = next(pending, None)
    for gs, ge in gaps:
        while nxt is not None and nxt[1] < ge:
            active.append(nxt)
            nxt = next(pending, None)
        active = [sp for sp in active if sp[2] > gs]
        yield active


def _inside(a: List[Interval], b: List[Interval]) -> float:
    """Length of the intersection of two sorted disjoint interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        total += _overlap(a[i], b[j])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def reduce_events(
    devices: Dict[str, List[Event]],
    harness: List[Event],
    program: List[ProgramSpan],
    top: int = 10,
) -> Optional[dict]:
    """``tracecut.reduce``'s result from events, idle gaps named by the
    program's spans where they cover them, plus ``spans``."""
    windows = [(s, e) for n, s, e in harness if n == WINDOW_SPAN]
    if not windows or not any(devices.values()):
        return None
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    inner = sorted(((n, s, e) for n, s, e in harness if n != WINDOW_SPAN), key=lambda x: x[1])
    prog = sorted(program, key=lambda x: x[1])
    names = sorted({sp[0] for sp in prog if sp[2] > lo and sp[1] < hi})
    unions = {
        n: _clip(_union([(sp[1], sp[2]) for sp in prog if sp[0] == n]), lo, hi)
        for n in names
    }
    busy, op_time = [], defaultdict(float)
    gap_time, span_busy = defaultdict(float), defaultdict(float)
    for ops in devices.values():
        if not ops:
            continue
        for name, s, e in ops:
            op_time[name] += _overlap((s, e), (lo, hi))
        covered = _clip(_union([(s, e) for _, s, e in ops]), lo, hi)
        busy.append(sum(e - s for s, e in covered))
        for n in names:
            span_busy[n] += _inside(unions[n], covered)
        edges = [lo] + [x for iv in covered for x in iv] + [hi]
        gaps = [(gs, ge) for gs, ge in zip(edges[::2], edges[1::2]) if ge > gs]
        for gap, h, p in zip(gaps, _sweep(inner, gaps), _sweep(prog, gaps)):
            pieces = _pieces(gap, p, h) if p else [(*gap, _harness_label(gap, h))]
            for a, b, label in pieces:
                gap_time[label] += (b - a) / len(devices)
    by_time = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]  # noqa: E731
    out = {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[n, t / 1e9 / len(busy)] for n, t in by_time(op_time)],
        "idle_gaps": [[n, t / 1e9] for n, t in by_time(gap_time)],
    }
    if program:
        out["spans"] = {
            n[len(PROGRAM_PREFIX):]: {
                "count": sum(1 for sp in prog if sp[0] == n and sp[2] > lo and sp[1] < hi),
                "seconds": sum(e - s for s, e in unions[n]) / 1e9,
                "busy_s": span_busy[n] / len(busy) / 1e9,
            }
            for n in names
        }
    return out
