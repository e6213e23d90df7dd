"""Recall arithmetic, on cases written out by hand."""
import pytest
from tinycell import ROOT  # noqa: F401

from bench import truth


def test_all_found_in_any_order():
    assert truth.recall_at_k([[3, 2, 1]], [[1, 2, 3]], 3) == 1.0


def test_partial_overlap():
    assert truth.recall_at_k([[1, 9, 8]], [[1, 2, 3]], 3) == pytest.approx(1 / 3)


def test_mean_over_queries():
    r = truth.recall_at_k([[1, 2], [7, 8]], [[1, 2], [5, 6]], 2)
    assert r == pytest.approx(0.5)


def test_padding_in_truth_is_not_counted():
    # two real neighbours, one pad: both found -> 1.0
    assert truth.recall_at_k([[4, 5, 6]], [[4, 5, -1]], 3) == 1.0


def test_padding_in_retrieved_never_hits_padding_in_truth():
    assert truth.recall_at_k([[-1, -1, 4]], [[4, -1, -1]], 3) == 1.0
    assert truth.recall_at_k([[-1, -1, -1]], [[4, 5, -1]], 3) == 0.0


def test_empty_truth_scores_one():
    assert truth.recall_at_k([[1, 2]], [[-1, -1]], 2) == 1.0


def test_only_first_k_columns_count():
    assert truth.recall_at_k([[9, 1]], [[1, 2]], 1) == 0.0


def test_shape_mismatch_raises():
    with pytest.raises(ValueError):
        truth.recall_at_k([[1, 2]], [[1, 2], [3, 4]], 2)
