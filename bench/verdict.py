"""What decides ``correct``: the program's answers against the plain
reference (``reference.py``), one number per layer the comparison
covers, each with a limit set from measured readings (``limits.json``:
the largest reading of sound runs, the smallest of the lower-precision
control, and the limit between them).

  codes_mismatch  share of base rows whose stored codes differ from the
                  reference's ICM codes of the same rows (encode layer);
  lists_misfiled  (IVF) share of base rows the program's inverted lists
                  hold nowhere, twice, or under a centroid farther than
                  the nearest by more than the program's rounding allows
                  (index build);
  ids_missed      share of the sampled answers' slots (query, rank)
                  that hold no row, repeat a row, hold a row the search
                  cannot reach (IVF: in no list the probe can take), or
                  hold a row farther than the reference's k-th neighbour
                  (a row tied with it to within ``TIE`` of the LUT range
                  counts as one of the k): the search's ids;
  dist_gap        widest gap, over the sampled answers' rows, between
                  the distance the program reports for a row and the
                  reference's distance of that row, as a share of the
                  query's LUT range (the search's distances).

A number that is not finite fails.
"""
from __future__ import annotations

import json
import math
import os

import numpy as np

TIE = 1e-6                  # distances this close (share of LUT range) tie
LIMITS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "limits.json")


def load_limits(path: str = LIMITS_FILE) -> dict:
    with open(path) as f:
        return {k: float(v["limit"]) for k, v in json.load(f).items()}


def codes_mismatch(program_codes, reference_codes) -> float:
    a = np.asarray(program_codes).astype(np.int64)
    b = np.asarray(reference_codes).astype(np.int64)
    if a.shape != b.shape:
        return math.inf
    return float(np.mean(np.any(a != b, axis=1)))


def ids_missed(program_ids, answer_dists, reference_dists, lut_range,
               reachable=None) -> float:
    """``answer_dists``: the reference's distance of each row the program
    answered (+inf for no row); ``reference_dists``: the reference's own
    top-k distances, ascending; ``reachable``: whether the search can
    reach each answered row (None: every row)."""
    p = np.asarray(program_ids)
    a = np.asarray(answer_dists, np.float64)
    r = np.asarray(reference_dists, np.float64)
    reach = (np.ones(p.shape, bool) if reachable is None
             else np.asarray(reachable, bool))
    if p.shape != r.shape or a.shape != p.shape or reach.shape != p.shape:
        return math.inf
    kth = r[:, -1:] + TIE * np.asarray(lut_range, np.float64)[:, None]
    srt = np.sort(p, axis=1)
    repeat = np.zeros(p.shape, bool)
    repeat[:, 1:] = (srt[:, 1:] == srt[:, :-1]) & (srt[:, 1:] >= 0)
    bad = ~(a <= kth) | (p < 0) | ~reach
    return float((bad.sum() + repeat.sum()) / p.size)


def dist_gap(program_dists, answer_dists, lut_range) -> float:
    p = np.asarray(program_dists, np.float64)
    a = np.asarray(answer_dists, np.float64)
    if p.shape != a.shape:
        return math.inf
    ok = np.isfinite(a)
    gap = np.abs(p - a) / np.asarray(lut_range, np.float64)[:, None]
    gap = np.where(np.isnan(gap), np.inf, gap)[ok]
    return float(gap.max()) if gap.size else math.inf


def decide(numbers: dict, limits: dict) -> tuple:
    """(correct, checks): every number at or under its limit; checks is
    ``{name: {"value", "limit"}}`` in a fixed order."""
    checks = {}
    ok = True
    for name in sorted(numbers):
        v = float(numbers[name])
        lim = limits[name]
        passed = math.isfinite(v) and v <= lim
        ok = ok and passed
        checks[name] = {"value": v, "limit": lim}
    return ok, checks
