"""Exact L2 neighbours and recall, kept with the benchmark so that a
change to the program cannot move the yardstick.  The recall
arithmetic is that of ``repro.eval.recall_at_k`` (set overlap, ``-1``
padding never counts)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import blocks

HIGHEST = jax.lax.Precision.HIGHEST
ROW_BLOCK = 262144          # base rows per block of the running top-k


@functools.partial(jax.jit, static_argnames=("k", "rows", "block"))
def _merge_rows(queries, base, start, fresh, best, *, k: int, rows: int,
                block: int):
    """``best``, the k nearest so far of each query (in blocks of
    ``block`` queries), merged with base rows start .. start + rows - 1;
    those below ``fresh`` were merged before and read +inf."""
    nq, d = queries.shape
    qp = jnp.pad(queries, ((0, (-nq) % block), (0, 0))).reshape(-1, block, d)
    xb = jax.lax.dynamic_slice_in_dim(base, start, rows)
    ids = start + jnp.arange(rows, dtype=jnp.int32)
    xsq = jnp.einsum("nd,nd->n", xb, xb, precision=HIGHEST)

    def one(args):
        qs, d2_best, ids_best = args
        d2 = (jnp.einsum("qd,qd->q", qs, qs, precision=HIGHEST)[:, None]
              - 2.0 * jnp.dot(qs, xb.T, precision=HIGHEST) + xsq[None, :])
        return blocks.merge_topk(d2_best, ids_best,
                                 jnp.where(ids >= fresh, d2, jnp.inf), ids,
                                 k)

    return jax.lax.map(one, (qp, *best))


def exact_neighbours(queries, base, k: int, block: int = 128,
                     rows: int = ROW_BLOCK):
    """(ids (nq, k) int32, squared distances (nq, k) f32), on the host, of
    the k nearest base rows of each query, computed on the device at
    ``Precision.HIGHEST`` in blocks of ``block`` queries: a running top-k
    over blocks of ``rows`` base rows, one call each, so that no program
    holds the whole base twice.  Ties go to the lower id."""
    queries, base = jnp.asarray(queries), jnp.asarray(base)
    nq, n, k = queries.shape[0], base.shape[0], int(k)
    rows = min(int(rows), n)
    shape = (-(-nq // block), block, k)
    best = (jnp.asarray(np.full(shape, np.inf, np.float32)),
            jnp.asarray(np.full(shape, -1, np.int32)))
    # a loop inside one program would have the TPU lay the whole base out
    # anew for it (an (n, 96) f32 array is stored column-major there)
    for fresh in range(0, n, rows):
        best = _merge_rows(queries, base, min(fresh, n - rows), fresh, best,
                           k=k, rows=rows, block=int(block))
    d2, ids = (np.asarray(a).reshape(-1, k)[:nq] for a in best)
    return ids, d2


def recall_per_query(retrieved, truth, k: int) -> np.ndarray:
    """|retrieved[:k] ∩ truth[:k]| / |valid truth[:k]| per query; ids
    below 0 are padding.  A query with no valid truth scores 1."""
    r = np.asarray(retrieved)[:, :k]
    t = np.asarray(truth)[:, :k]
    if r.ndim != 2 or t.ndim != 2 or r.shape[0] != t.shape[0]:
        raise ValueError(f"recall: expected (nq, r) and (nq, t) ids, got "
                         f"{r.shape} and {t.shape}")
    valid_t = t >= 0
    hits = ((r[:, :, None] == t[:, None, :]) & valid_t[:, None, :]
            & (r >= 0)[:, :, None])
    inter = hits.any(axis=1).sum(axis=1)
    n_true = valid_t.sum(axis=1)
    return np.where(n_true > 0, inter / np.maximum(n_true, 1), 1.0)


def recall_at_k(retrieved, truth, k: int) -> float:
    """Mean of ``recall_per_query``."""
    return float(recall_per_query(retrieved, truth, k).mean())
