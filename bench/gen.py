"""SIFT-shaped vectors generated on the device from one seed.

Local image descriptors repeat across images, so real SIFT data is many
tight clusters, each spread over a few directions, on a non-negative
orthant.  The generator draws:

  centers   ``clusters`` points ``mean + z @ G`` where z has a decaying
            per-direction scale ``center_std * i**-center_decay`` and G
            is a random (d, d) mixing matrix (a global spectrum like
            SIFT's principal components);
  sizes     cluster probabilities ``softmax(size_sigma * N(0, 1))`` — a
            lognormal spread of cluster sizes;
  rows      each row picks a cluster, adds ``B_c @ (s * u)`` with B_c a
            random (d, rank) basis of its own and ``s_i = within_std *
            i**-within_decay``, adds isotropic ``noise_std`` noise and is
            clipped at 0 (SIFT bins are non-negative).

Every row is an independent draw, so learn, base and query rows are
disjoint draws of one distribution (held-out queries, as SIFT's own
query set is).  The same seed gives the same rows on the same platform.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

BLOCK_ROWS = 16384          # rows per generated block (bounds the basis gather)


def seed_key(seed: int):
    """A PRNG key for any whole-number seed, 2**31 and above included."""
    s = int(seed) % (1 << 62)
    return jax.random.fold_in(jax.random.PRNGKey(s & 0x7FFFFFFF), s >> 31)


@functools.partial(jax.jit, static_argnames=("n", "d", "p"))
def _rows(key, *, n: int, d: int, p: tuple):
    q = dict(p)
    nc, r = int(q["clusters"]), int(q["rank"])
    k_c, k_g, k_b, k_w, k_rows = jax.random.split(key, 5)
    center_scale = q["center_std"] * jnp.arange(1, d + 1,
                                                dtype=jnp.float32) \
        ** -q["center_decay"]
    mix = jax.random.normal(k_g, (d, d), jnp.float32) / jnp.sqrt(d)
    centers = q["mean"] + (jax.random.normal(k_c, (nc, d), jnp.float32)
                           * center_scale) @ mix
    bases = jax.random.normal(k_b, (nc, d, r), jnp.float32) / jnp.sqrt(d)
    within = q["within_std"] * jnp.arange(1, r + 1, dtype=jnp.float32) \
        ** -q["within_decay"]
    logits = q["size_sigma"] * jax.random.normal(k_w, (nc,), jnp.float32)
    n_blocks = -(-n // BLOCK_ROWS)

    def block(i):
        kb = jax.random.fold_in(k_rows, i)
        k_id, k_u, k_e = jax.random.split(kb, 3)
        cid = jax.random.categorical(k_id, logits, shape=(BLOCK_ROWS,))
        u = jax.random.normal(k_u, (BLOCK_ROWS, r), jnp.float32) * within
        e = jax.random.normal(k_e, (BLOCK_ROWS, d), jnp.float32)
        x = (centers[cid]
             + jnp.einsum("ndr,nr->nd", bases[cid], u,
                          precision=jax.lax.Precision.HIGHEST)
             + q["noise_std"] * e)
        return jnp.maximum(x, 0.0)

    out = jax.lax.map(block, jnp.arange(n_blocks))
    return out.reshape(-1, d)[:n]


def make_rows(seed: int, n: int, d: int, params: dict):
    """(n, d) float32 rows on the default device, from ``seed``."""
    p = tuple(sorted((k, float(v)) for k, v in params.items()))
    return _rows(seed_key(seed), n=int(n), d=int(d), p=p)


def split(rows, n_learn: int, n_base: int, n_queries: int):
    """learn / base / query pool from one block of independent draws."""
    a, b = n_learn, n_learn + n_base
    return rows[:a], rows[a:b], rows[b:b + n_queries]
