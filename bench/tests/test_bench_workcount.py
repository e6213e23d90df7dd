"""Work counts against hand counts, and the peaks table."""
import pytest
from tinycell import ROOT  # noqa: F401

from bench import peaks, workcount


def test_crude_counts_fast_codes_once_and_adds_per_query():
    # 4 queries scan 10 rows each; 10 distinct rows; 2 fast codebooks
    w = workcount.crude(scanned=40, rows_read=10, k_fast=2)
    assert w == workcount.Work(ops=80, bytes=20)


def test_refine_counts_slow_codes_of_survivors():
    # K=8, 2 fast: 6 slow codes; 7 survivors in all, 5 distinct rows
    w = workcount.refine(survivors=7, rows_read=5, k=8, k_fast=2)
    assert w == workcount.Work(ops=42, bytes=30)


def test_tables_and_probe():
    assert workcount.tables(nq=2, k=3, m=4) == workcount.Work(0.0, 96.0)
    # 2 queries x 3 lists x d=5: 2*2*3*5 ops; 3*5 f32 centroids
    assert workcount.probe(nq=2, n_lists=3, d=5) == workcount.Work(60.0, 60.0)


def test_work_adds():
    assert (workcount.Work(1, 2) + workcount.Work(3, 4)) == workcount.Work(4, 6)


def test_least_seconds_takes_the_larger_bound():
    p = {"hbm_bytes_per_s": 100.0, "bf16_flops": 1000.0}
    assert workcount.least_seconds(workcount.Work(ops=10, bytes=50), p) \
        == (0.5, "memory")
    assert workcount.least_seconds(workcount.Work(ops=5000, bytes=50), p) \
        == (5.0, "compute")


def test_v5e_peaks():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["int8_ops"] == 393e12


def test_unknown_device_kind_raises():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v9 imaginary")
