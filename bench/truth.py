"""Exact L2 neighbours and recall, kept with the benchmark so that a
change to the program cannot move the yardstick.  The recall
arithmetic is that of ``repro.eval.recall_at_k`` (set overlap, ``-1``
padding never counts)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


@functools.partial(jax.jit, static_argnames=("k", "block"))
def _exact_topk(queries, base, *, k: int, block: int):
    xsq = jnp.einsum("nd,nd->n", base, base, precision=HIGHEST)
    nq = queries.shape[0]
    qp = jnp.pad(queries, ((0, (-nq) % block), (0, 0)))

    def one(qs):
        d2 = (jnp.einsum("qd,qd->q", qs, qs, precision=HIGHEST)[:, None]
              - 2.0 * jnp.dot(qs, base.T, precision=HIGHEST) + xsq[None, :])
        neg, ids = jax.lax.top_k(-d2, k)
        return ids, -neg

    ids, d2 = jax.lax.map(one, qp.reshape(-1, block, queries.shape[1]))
    return ids.reshape(-1, k)[:nq], d2.reshape(-1, k)[:nq]


def exact_neighbours(queries, base, k: int, block: int = 128):
    """(ids (nq, k) int32, squared distances (nq, k) f32) of the k
    nearest base rows of each query, computed on the device at
    ``Precision.HIGHEST`` in blocks of ``block`` queries."""
    return _exact_topk(jnp.asarray(queries), jnp.asarray(base), k=int(k),
                       block=int(block))


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
