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


@functools.partial(jax.jit, static_argnames=("sizes", "d", "p"))
def _parts(key, *, sizes: tuple, d: int, p: tuple):
    """Consecutive ranges of the draw, ``sizes`` rows each, in one
    program.  The draw's blocks are fixed (block i from ``fold_in(k_rows,
    i)``); each range is written block by block into a buffer of its own,
    a block that straddles an end of the range rolled into the buffer's
    first or last ``BLOCK_ROWS`` rows and masked."""
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

    j = jnp.arange(BLOCK_ROWS)

    def part(start, n):
        size = max(n, BLOCK_ROWS)

        def put(i, out):
            o = i * BLOCK_ROWS - start      # buffer row of the block's row 0
            c = jnp.clip(o, 0, size - BLOCK_ROWS)
            s = c - o                       # buffer row c + j: block row j + s
            keep = (j + s >= 0) & (j + s < BLOCK_ROWS) & (c + j < n)
            x = jnp.roll(block(i), -s, axis=0)
            old = jax.lax.dynamic_slice_in_dim(out, c, BLOCK_ROWS)
            return jax.lax.dynamic_update_slice_in_dim(
                out, jnp.where(keep[:, None], x, old), c, 0)

        out = jax.lax.fori_loop(start // BLOCK_ROWS,
                                -(-(start + n) // BLOCK_ROWS), put,
                                jnp.zeros((size, d), jnp.float32))
        return out if size == n else out[:n]

    starts = [sum(sizes[:i]) for i in range(len(sizes))]
    return tuple(part(a, n) for a, n in zip(starts, sizes))


def make_parts(seed: int, sizes, d: int, params: dict) -> list:
    """Consecutive parts of the draw from ``seed`` (learn, base, query
    pool), ``sizes`` rows each, as (n, d) float32 arrays on the default
    device: the same bits as those ranges of one longer draw, made in one
    program that never holds the whole draw beside its parts."""
    p = tuple(sorted((k, float(v)) for k, v in params.items()))
    return list(_parts(seed_key(seed), sizes=tuple(int(n) for n in sizes),
                       d=int(d), p=p))


def make_rows(seed: int, n: int, d: int, params: dict):
    """(n, d) float32 rows on the default device, from ``seed``."""
    return make_parts(seed, (n,), d, params)[0]
