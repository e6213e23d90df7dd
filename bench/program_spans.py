"""The program's own spans in a traced run: the ``repro.*`` host spans
that ``src/repro/obs.py`` opens, on the profiler's clock, for the
per-layer metrics that read them.

``load(ctx)`` opens the trace that the run wrote (``bench/run.py``'s
``OUT_DIR/trace``, read with ``tracing.find_xplane``/``from_xplane``,
once per process), keeps each host line's ``repro.*`` events, and clips
them to the window of ``ctx["trace"]`` (a ``tracing.Reduced``), whose
device operations give the busy and idle intervals.  It returns None
where the trace holds no program span, as a program without them
writes.

A layer's host time is its span's duration minus the union of the
``repro.engine.wait`` spans inside it on the same line: the part of the
span in which the host was not waiting for the device.
"""
from __future__ import annotations

import os
from typing import Dict, List, Optional, Tuple

from bench import tracing

PREFIX = "repro."
WAIT = "repro.engine.wait"

Interval = Tuple[int, int]

_parsed: Dict[tuple, dict] = {}


def _trace_dict() -> Optional[dict]:
    """The traced run's trace as a plain dict, parsed once per file."""
    from bench.run import OUT_DIR

    try:
        path = tracing.find_xplane(os.path.join(OUT_DIR, "trace"))
    except FileNotFoundError:
        return None
    st = os.stat(path)
    key = (path, st.st_mtime_ns, st.st_size)
    if key not in _parsed:
        _parsed.clear()
        _parsed[key] = tracing.from_xplane(path)
    return _parsed[key]


def load(ctx: dict) -> Optional["Spans"]:
    red = ctx.get("trace")
    if red is None:
        return None
    trace = _trace_dict()
    if trace is None:
        return None
    spans = Spans(trace, red)
    return spans if spans.lines else None


def union(intervals: List[Interval]) -> List[Interval]:
    return tracing._merge(list(intervals))


def overlap(a: List[Interval], b: List[Interval]) -> int:
    """Nanoseconds in which both merged interval lists are open."""
    i = j = tot = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            tot += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


class Spans:
    """The ``repro.*`` spans of each host line, clipped to the window."""

    def __init__(self, trace: dict, red: tracing.Reduced):
        self.red = red
        self.t0, self.t1 = red.t0, red.t1
        self.lines: Dict[tuple, List[Tuple[str, int, int]]] = {}
        for p, plane in enumerate(trace["planes"]):
            if tracing.DEVICE_PLANE.match(plane["name"]):
                continue
            for i, line in enumerate(plane["lines"]):
                evs = sorted(((n, max(s, self.t0), min(s + d, self.t1))
                              for n, s, d in line["events"]
                              if n.startswith(PREFIX) and s + d > self.t0
                              and s < self.t1), key=lambda ev: ev[1])
                if evs:
                    self.lines[(p, i)] = evs

    def named(self, name: str, line=None) -> List[Tuple[tuple, int, int]]:
        """(line, start, end) of every span called ``name``."""
        return [(k, s, e) for k, evs in self.lines.items()
                if line is None or k == line
                for n, s, e in evs if n == name]

    def host_ns(self, name: str) -> List[int]:
        """Each ``name`` span's duration minus the union of the
        ``repro.engine.wait`` spans inside it on its line."""
        out, by_line = [], {}
        for k, s, e in self.named(name):
            if k not in by_line:
                by_line[k] = self.named(WAIT, k)
            waits = union([(max(ws, s), min(we, e))
                           for _, ws, we in by_line[k]
                           if we > s and ws < e])
            out.append((e - s) - sum(b - a for a, b in waits))
        return out

    def durations_ns(self, name: str) -> List[int]:
        return [e - s for _, s, e in self.named(name)]

    def line_of(self, name: str):
        """The line that holds the most ``name`` spans (None: none)."""
        counts = {k: sum(1 for n, _, _ in evs if n == name)
                  for k, evs in self.lines.items()}
        best = max(counts, key=counts.get, default=None)
        return best if best is not None and counts[best] else None

    def device_idle(self) -> Optional[List[Interval]]:
        """The window's idle intervals on the first device (None: the
        trace holds no device that ran an operation)."""
        if not self.red.devices:
            return None
        idle, prev = [], self.t0
        for s, e in self.red._busy(self.red.devices[0]):
            if s > prev:
                idle.append((prev, s))
            prev = max(prev, e)
        if self.t1 > prev:
            idle.append((prev, self.t1))
        return idle
