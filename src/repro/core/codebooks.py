"""Codebook containers and initialization for additive/product quantizers.

A quantizer is an array C of shape (K, m, d): K codebooks of m codewords
in R^d.  PQ constrains codebook k to a contiguous d/K slice; ICQ
constrains the *fast* group to the learned subspace psi and the rest to
its complement — with the nonzero coordinates interleaved, not
contiguous (paper §3.1).

Initializers: k-means (Lloyd, matmul-based assignment) for PQ subspaces,
residual k-means for additive codebooks (each codebook fit on the
residual of the previous ones — the standard CQ/AQ warm start).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


# --------------------------------------------------------------- k-means ----

def kmeans_assign(x, cent):
    """Nearest-centroid ids.  x: (n,d), cent: (m,d) -> (n,) int32.

    Matmul formulation (MXU-friendly): argmin_m ||x||^2 - 2 x.c + ||c||^2;
    the ||x||^2 term is constant in m and dropped.
    """
    scores = -2.0 * x @ cent.T + jnp.sum(jnp.square(cent), axis=-1)[None, :]
    return jnp.argmin(scores, axis=-1).astype(jnp.int32)


def kmeans_update(x, ids, m: int):
    """Mean of assigned points per centroid; empty centroids keep position 0
    count guard (caller re-seeds)."""
    d = x.shape[-1]
    sums = jnp.zeros((m, d), jnp.float32).at[ids].add(x.astype(jnp.float32))
    cnts = jnp.zeros((m,), jnp.float32).at[ids].add(1.0)
    return sums / jnp.maximum(cnts, 1.0)[:, None], cnts


def kmeans(key, x, m: int, iters: int = 25):
    """Lloyd's k-means.  Returns (centroids (m,d), ids (n,)).

    Empty clusters are re-seeded to the points currently farthest from
    their centroid (standard fix; keeps m effective codewords).
    """
    n = x.shape[0]
    x = jnp.asarray(x, jnp.float32)
    init_ids = jax.random.choice(key, n, (m,), replace=False)
    cent0 = x[init_ids]

    def body(cent, k):
        ids = kmeans_assign(x, cent)
        new, cnts = kmeans_update(x, ids, m)
        # re-seed empties at far points
        d2 = jnp.sum(jnp.square(x - cent[ids]), axis=-1)
        far = jnp.argsort(-d2)[:m]
        new = jnp.where((cnts > 0)[:, None], new, x[far])
        return new, None

    cent, _ = jax.lax.scan(body, cent0, jnp.arange(iters))
    return cent, kmeans_assign(x, cent)


# --------------------------------------------------------- initializers ----

def init_pq(key, x, num_codebooks: int, m: int, iters: int = 25):
    """PQ init: k-means per contiguous subspace, embedded back into R^d.

    Returns C: (K, m, d) with codebook k nonzero only on its slice.
    """
    n, d = x.shape
    K = num_codebooks
    assert d % K == 0, (d, K)
    sub = d // K
    cbs = []
    for k in range(K):
        xs = x[:, k * sub: (k + 1) * sub]
        cent, _ = kmeans(jax.random.fold_in(key, k), xs, m, iters)
        full = jnp.zeros((m, d), jnp.float32)
        full = full.at[:, k * sub: (k + 1) * sub].set(cent)
        cbs.append(full)
    return jnp.stack(cbs)


def init_residual(key, x, num_codebooks: int, m: int, iters: int = 25,
                  mask=None):
    """Residual k-means init for additive codebooks (CQ/ICQ warm start).

    ``mask``: optional (K, d) 0/1 — support constraint per codebook (ICQ:
    fast codebooks masked to psi, slow to the complement).  Each codebook
    is fit on the (masked) residual of the previous ones.
    """
    n, d = x.shape
    res = x.astype(jnp.float32)
    cbs = []
    for k in range(num_codebooks):
        tgt = res * mask[k][None, :] if mask is not None else res
        cent, ids = kmeans(jax.random.fold_in(key, 101 + k), tgt, m, iters)
        if mask is not None:
            cent = cent * mask[k][None, :]
        cbs.append(cent)
        res = res - cent[ids]
    return jnp.stack(cbs)


# ------------------------------------------------------------ geometry ----

def codeword_sq_norms(C):
    """||c||^2 per codeword.  C: (K,m,d) -> (K,m).

    A full-precision contraction rather than a square-and-sum: XLA's CPU
    backend reduces a fused square-and-sum in another order than an
    unfused one, so the sum's last bit would depend on the program it is
    traced into (eager vs jit, a query chunk vs the whole batch)."""
    return jnp.einsum("kmd,kmd->km", C, C,
                      precision=jax.lax.Precision.HIGHEST)


def cross_gram(C):
    """Pairwise codeword inner products between codebooks.

    C: (K,m,d) -> G: (K,K,m,m) with G[j,k] = C_j @ C_k^T.  Used by ICM
    encoding (the cross-codebook interaction term) and the CQ penalty.
    """
    return jnp.einsum("jmd,knd->jkmn", C, C)


def decode(C, codes):
    """Decode codes (n,K) against C (K,m,d) -> (n,d)."""
    K = C.shape[0]
    parts = [C[k][codes[:, k]] for k in range(K)]
    return sum(parts)


def quantization_mse(x, C, codes):
    """Mean squared quantization error ||x - decode(codes)||^2 / n."""
    return jnp.mean(jnp.sum(jnp.square(x - decode(C, codes)), axis=-1))
