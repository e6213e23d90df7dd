"""From a profiler trace to the numbers the benchmark reports: device
busy time and idle share, the device time of named operations, the
operations that took most time, and the longest idle gaps named by the
benchmark's host span that covered them.

A trace is first turned into a plain dict, ``{"planes": [{"name",
"lines": [{"name", "events": [[name, start_ns, duration_ns], ...]}]}]}``
(``from_xplane``), so the reduction runs the same on a recorded, trimmed
trace in a test as on a fresh one.  Device planes are ``/device:TPU:<i>``
and their operations sit on the ``XLA Ops`` line; the benchmark's own
spans (``jax.profiler.TraceAnnotation`` names starting ``bench.``) sit
on host lines.  ``bench.window`` marks the measured window; everything
is clipped to it.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def op_name(text: str) -> str:
    """An XLA op event is named by its whole HLO instruction
    (``%crude_topk_pallas.1 = (f32[...]) custom-call(...)``); keep the
    instruction's own name, ``crude_topk_pallas.1``."""
    return text.split(" = ", 1)[0].lstrip("%")


def from_xplane(path: str) -> dict:
    """The trace at ``path`` as a plain dict (see module docstring)."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in pd.planes:
        dev = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            evs = [[op_name(e.name) if dev else e.name, int(e.start_ns),
                    int(e.duration_ns)] for e in line.events]
            lines.append({"name": line.name, "events": evs})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _merge(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


class Reduced:
    """The reduction of one trace over its ``bench.window`` span."""

    def __init__(self, trace: dict, window_span: str = WINDOW_SPAN):
        self.spans: List[Tuple[str, int, int]] = []
        self.ops: Dict[str, List[Tuple[str, int, int]]] = {}
        for plane in trace["planes"]:
            dev = bool(DEVICE_PLANE.match(plane["name"]))
            for line in plane["lines"]:
                if dev and line["name"] == OPS_LINE:
                    self.ops[plane["name"]] = [
                        (n, s, s + d) for n, s, d in line["events"]]
                elif not dev:
                    self.spans += [(n, s, s + d) for n, s, d in
                                   line["events"]
                                   if n.startswith(SPAN_PREFIX)]
        wins = [(s, e) for n, s, e in self.spans if n == window_span]
        if not wins:
            raise ValueError(f"the trace holds no {window_span!r} span")
        self.t0, self.t1 = wins[0]
        # only devices that ran something count (one chip of four idle
        # all window long would halve every share)
        self.ops = {p: [(n, max(s, self.t0), min(e, self.t1))
                        for n, s, e in evs if e > self.t0 and s < self.t1]
                    for p, evs in self.ops.items()}
        self.ops = {p: evs for p, evs in self.ops.items() if evs}

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    @property
    def devices(self) -> List[str]:
        return sorted(self.ops)

    def _busy(self, plane: str) -> List[Tuple[int, int]]:
        return _merge([(s, e) for _, s, e in self.ops[plane]])

    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over devices."""
        if not self.ops:
            return 0.0
        tot = [sum(e - s for s, e in self._busy(p)) for p in self.ops]
        return sum(tot) / len(tot) / 1e9

    def idle_share(self) -> Optional[float]:
        if not self.ops or self.window_s <= 0:
            return None
        return 1.0 - self.busy_s() / self.window_s

    def op_seconds(self, patterns: Sequence[str]) -> float:
        """Device seconds of operations whose name matches any of
        ``patterns`` (regular expressions), averaged over devices."""
        if not self.ops:
            return 0.0
        rx = [re.compile(p) for p in patterns]
        tot = 0
        for evs in self.ops.values():
            tot += sum(e - s for n, s, e in evs
                       if any(r.search(n) for r in rx))
        return tot / len(self.ops) / 1e9

    def op_chips(self, patterns: Sequence[str]) -> int:
        """How many devices ran an operation whose name matches any of
        ``patterns``."""
        rx = [re.compile(p) for p in patterns]
        return sum(any(r.search(n) for n, _, _ in evs for r in rx)
                   for evs in self.ops.values())

    def top_ops(self, n: int = 10) -> List[list]:
        """The ``n`` operation names with the most device time, summed
        over their events and averaged over devices."""
        acc: Dict[str, int] = {}
        for evs in self.ops.values():
            for name, s, e in evs:
                acc[name] = acc.get(name, 0) + (e - s)
        k = max(len(self.ops), 1)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[name, t / k / 1e9] for name, t in top]

    def _span_at(self, t: int) -> str:
        """The innermost benchmark span covering instant ``t``."""
        best = None
        for name, s, e in self.spans:
            if name != WINDOW_SPAN and s <= t < e:
                if best is None or e - s < best[1]:
                    best = (name, e - s)
        return best[0] if best else "no span"

    def idle_gaps(self, n: int = 10) -> List[list]:
        """The ``n`` longest idle gaps of the first device in the window,
        each named by the host span covering its middle."""
        if not self.ops:
            return []
        busy = self._busy(self.devices[0])
        gaps, prev = [], self.t0
        for s, e in busy:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        if self.t1 > prev:
            gaps.append((prev, self.t1))
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self._span_at((s + e) // 2), (e - s) / 1e9]
                for s, e in gaps[:n]]

    def breakdown(self, n: int = 10) -> dict:
        return {"device_ops": self.top_ops(n), "idle_gaps": self.idle_gaps(n)}
