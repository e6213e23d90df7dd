"""The compared numbers, on hand-made answers."""
import math

import numpy as np
import pytest
from tinycell import ROOT  # noqa: F401

from bench import verdict


def test_codes_mismatch_counts_rows():
    a = np.array([[1, 2], [3, 4], [5, 6], [7, 8]], np.uint8)
    b = a.copy()
    b[2, 1] = 0
    assert verdict.codes_mismatch(a, b) == 0.25
    assert verdict.codes_mismatch(a, b[:3]) == math.inf


def test_ids_missed_ignores_order():
    ref = np.array([[1.0, 2.0, 3.0], [4.0, 5.0, 6.0]])
    rng = np.ones(2)
    ids = np.array([[3, 2, 1], [6, 5, 4]])
    assert verdict.ids_missed(ids, ref[:, ::-1], ref, rng) == 0.0
    worse = np.array([[3.0, 2.0, 9.0], [6.0, 5.0, 4.0]])
    assert verdict.ids_missed(ids, worse, ref, rng) == pytest.approx(1 / 6)


def test_ids_missed_counts_no_row_and_repeats_but_not_ties():
    ref = np.array([[1.0, 2.0, 3.0]])
    rng = np.ones(1)
    # a row tied with the k-th neighbour is one of the k
    assert verdict.ids_missed(np.array([[7, 8, 9]]),
                              np.array([[1.0, 2.0, 3.0 + 1e-7]]), ref,
                              rng) == 0.0
    assert verdict.ids_missed(np.array([[7, 7, 9]]),
                              np.array([[1.0, 1.0, 3.0]]), ref,
                              rng) == pytest.approx(1 / 3)
    assert verdict.ids_missed(np.array([[7, -1, 9]]),
                              np.array([[1.0, np.inf, 3.0]]), ref,
                              rng) == pytest.approx(1 / 3)
    assert verdict.ids_missed(np.array([[7, 8]]), np.ones((1, 2)), ref,
                              rng) == math.inf


def test_dist_gap_is_the_widest_relative_gap():
    ref = np.array([[1.0, 2.0], [3.0, 4.0]])
    prog = np.array([[1.0, 2.5], [3.0, 4.0]])
    assert verdict.dist_gap(prog, ref, np.array([10.0, 1.0])) == 0.05
    assert verdict.dist_gap(np.full((2, 2), np.nan), ref, [1.0, 1.0]) \
        == math.inf
    # a slot that holds no row is ids_missed's, not a distance gap
    no_row = np.array([[1.0, np.inf], [3.0, 4.0]])
    assert verdict.dist_gap(prog, no_row, np.ones(2)) == 0.0


def test_decide_fails_a_number_over_its_limit_or_not_finite():
    lim = {"a": 1.0, "b": 2.0}
    ok, checks = verdict.decide({"a": 0.5, "b": 2.0}, lim)
    assert ok and checks["a"] == {"value": 0.5, "limit": 1.0}
    assert not verdict.decide({"a": 1.5, "b": 0.0}, lim)[0]
    assert not verdict.decide({"a": math.inf, "b": 0.0}, lim)[0]


def test_limits_file_names_every_compared_number():
    assert set(verdict.load_limits()) == {"codes_mismatch", "ids_missed",
                                          "dist_gap", "lists_misfiled"}


def test_ids_missed_counts_rows_the_search_cannot_reach():
    ref = np.array([[1.0, 2.0, 3.0]])
    ids = np.array([[7, 8, 9]])
    reach = np.array([[True, False, True]])
    assert verdict.ids_missed(ids, ref, ref, np.ones(1), reach) \
        == pytest.approx(1 / 3)
    assert verdict.ids_missed(ids, ref, ref, np.ones(1), reach[:, :2]) \
        == math.inf


def test_probe_band_holds_lists_within_rounding_of_the_cut():
    from bench import reference

    score = np.array([[0.0, 1.0, 2.0, 2.0005, 5.0]], np.float32)
    tol = np.full_like(score, 1e-3)
    every, some = map(np.asarray, reference._probe_band(score, tol, 3))
    assert every.tolist() == [[True, True, False, False, False]]
    assert some.tolist() == [[True, True, True, True, False]]
    every, some = map(np.asarray,
                      reference._probe_band(score, tol * 1e-3, 3))
    assert (every == some).all() and every.sum() == 3
    near = np.asarray(reference._near(score[:, 1:], tol[:, 1:]))
    assert near.tolist() == [[True, False, False, False]]
    near = np.asarray(reference._near(score[:, 2:], tol[:, 2:]))
    assert near.tolist() == [[True, True, False]]
