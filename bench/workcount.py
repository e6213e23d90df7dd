"""The search algorithm's own work, counted from shapes and counts: what
any implementation of the two-step scan must read and add, whatever
kernel does it.  A code is one byte (m = 256); a LUT entry is an f32.

  crude   every scanned row's fast codes are read once per batch (the
          rows a batch shares are read once), and each query adds
          |K_fast| table entries per row it scans;
  refine  the slow codes of every row that survives for some query of
          the batch are read once, and each query adds K - |K_fast|
          entries per row that survives for it;
  probe   (IVF) the query-centroid products, 2 d operations each, and
          one read of the centroids;
  tables  every query's K x m f32 table is read once.
"""
from __future__ import annotations

from typing import NamedTuple


class Work(NamedTuple):
    ops: float
    bytes: float

    def __add__(self, other):
        return Work(self.ops + other.ops, self.bytes + other.bytes)


def crude(*, scanned: float, rows_read: float, k_fast: int) -> Work:
    """``scanned``: sum over the batch's queries of the rows each scans;
    ``rows_read``: distinct rows the batch scans."""
    return Work(ops=scanned * k_fast, bytes=rows_read * k_fast)


def refine(*, survivors: float, rows_read: float, k: int,
           k_fast: int) -> Work:
    """``survivors``: sum over queries of rows that pass eq. 2;
    ``rows_read``: distinct rows that pass for some query."""
    return Work(ops=survivors * (k - k_fast), bytes=rows_read * (k - k_fast))


def tables(*, nq: int, k: int, m: int) -> Work:
    return Work(ops=0.0, bytes=4.0 * nq * k * m)


def probe(*, nq: int, n_lists: int, d: int) -> Work:
    return Work(ops=2.0 * nq * n_lists * d, bytes=4.0 * n_lists * d)


def least_seconds(work: Work, peaks: dict) -> tuple:
    """(seconds, bound): the larger of bytes over HBM bandwidth and
    operations over the chip's peak rate (the bf16 rate: no arithmetic
    the chip does runs faster), and which of the two it is."""
    t_mem = work.bytes / peaks["hbm_bytes_per_s"]
    t_ops = work.ops / peaks["bf16_flops"]
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")
