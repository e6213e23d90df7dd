"""Load drivers: the closed loop of one client sending batches, and the
open loop of Poisson arrivals.

The open loop times every request from when it was *due*, not from when
it was sent: a generator that falls behind (a slow submit, a stall of
the host) delays every later request, and that wait is part of what a
user would see.  How late the generator ran is reported beside it
(``late_ms`` per request).  Both drivers take the clock and the sleep as
arguments, so a test can drive them on a fake clock.
"""
from __future__ import annotations

import math
import time
from typing import Callable, List

import numpy as np


def poisson_due_times(rate_hz: float, seconds: float, *, gap_seed: int,
                      order_seed: int) -> np.ndarray:
    """Due times (s from the window's start) of a Poisson stream at
    ``rate_hz``.  The set of gaps comes from ``gap_seed`` alone; the
    run's seed only permutes their order, so every run offers the same
    gaps and about the same count, in another order."""
    if rate_hz <= 0 or seconds <= 0:
        raise ValueError(f"rate_hz and seconds must be > 0, got "
                         f"{rate_hz}, {seconds}")
    n = int(math.ceil(rate_hz * seconds * 1.25)) + 64
    gaps = np.random.default_rng(gap_seed).exponential(1.0 / rate_hz, n)
    gaps = np.random.default_rng(order_seed).permutation(gaps)
    due = np.cumsum(gaps) - gaps[0]
    return due[due < seconds]


def run_open(submit: Callable[[int], object], due: np.ndarray, *,
             clock: Callable[[], float] = time.perf_counter,
             sleep: Callable[[float], None] = time.sleep,
             span: Callable[[str], object] = None) -> List[dict]:
    """Send request i at ``due[i]`` (seconds after start) through
    ``submit(i)``, which returns a future.  Returns one record per
    request: ``due``, ``sent`` and ``done`` on one clock (``done`` is
    filled in when the future completes), ``future``, and ``error`` for
    a request ``submit`` refused."""
    span = span or no_span
    t0 = clock()
    records: List[dict] = []
    for i, t_due in enumerate(due):
        ahead = t_due - (clock() - t0)
        if ahead > 0:
            sleep(ahead)
        rec = {"i": i, "due": float(t_due), "sent": clock() - t0,
               "done": None, "future": None, "error": None}
        records.append(rec)
        try:
            with span("bench.submit"):
                fut = submit(i)
        except Exception as e:                      # noqa: BLE001
            rec["error"] = f"{type(e).__name__}: {e}"
            continue
        rec["future"] = fut
        fut.add_done_callback(
            lambda _f, r=rec: r.__setitem__("done", clock() - t0))
    return records


def settle(records: List[dict], *, timeout_s: float) -> None:
    """Wait for every sent request (at most ``timeout_s`` in all) and
    fill ``result`` or ``error``; a request that never completes keeps
    ``done`` None."""
    deadline = time.monotonic() + timeout_s
    for rec in records:
        fut = rec["future"]
        if fut is None:
            continue
        try:
            rec["result"] = fut.result(
                timeout=max(deadline - time.monotonic(), 0.0))
        except Exception as e:                      # noqa: BLE001
            rec["error"] = f"{type(e).__name__}: {e}"


def latencies_ms(records: List[dict]) -> np.ndarray:
    """Due-to-done latency of every completed request, in ms."""
    return np.asarray([(r["done"] - r["due"]) * 1e3 for r in records
                       if r["done"] is not None and r["error"] is None])


def late_ms(records: List[dict]) -> np.ndarray:
    """How late the generator sent each request, in ms."""
    return np.asarray([(r["sent"] - r["due"]) * 1e3 for r in records])


def run_closed(call: Callable[[int], object], seconds: float, *,
               clock: Callable[[], float] = time.perf_counter) -> tuple:
    """Call ``call(i)`` back to back until ``seconds`` have passed (the
    call that crosses the mark completes and counts).  Returns (results,
    elapsed seconds from the first call's start to the last's end)."""
    t0 = clock()
    out = []
    while clock() - t0 < seconds:
        out.append(call(len(out)))
    return out, clock() - t0


class _NoSpan:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def no_span(_name: str):
    return _NoSpan()
