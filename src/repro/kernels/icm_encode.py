"""Pallas TPU kernel: point-tiled ICM encoding for additive codebooks
(DESIGN.md §9).

The encoding hot path is the producer-side twin of the batched search
kernels: every database (or newly added) vector must be assigned K
additive codewords by Iterated Conditional Modes.  The seed formulation
materialized the full (K, K, m, m) cross-Gram plus a (K, n, m) query
tensor and swept codebooks with a vmap-of-gathers inner loop — memory
traffic far beyond what the arithmetic needs (kept as the oracle,
``kernels/ref.py::icm_encode_gram``).

This kernel uses the *residual* formulation instead: carrying the
current reconstruction ``recon = sum_k c_{k, b_k}`` per point makes the
codebook-k sweep step

    r      = recon - c_{k, b_k}                   # others-only partial sum
    scores = ||c_{k,j}||^2 - 2 <x - r, c_{k,j}>   # (blk_n, m)
    b_k    = argmin_j scores;  recon = r + c_{k, b_k}

— mathematically identical to the Gram-gather objective (the
interaction term <r, c_{k,j}> *is* the summed Gram row), but one
(blk_n, d) x (d, m) MXU matmul per codebook instead of K gathered
(blk_n, m) Gram rows, with no (K, K, m, m) or (K, n, m) materialization
at all.  Codeword gathers are one-hot matmuls (bit-exact vs a gather:
one 1.0 and zeros), the same trick as ``kernels/adc.py``.

Tiling: grid = (n / blk_n,) over point tiles; the codebooks C (K, m, d)
and their squared norms (K, m) are VMEM-pinned for the whole sweep
(K*m*d*4B — 128 KB at the seed config, orders below the Gram's 16 MB),
and each point tile runs all ``iters`` sweeps in-register before the
codes tile is written back once.  Warm start (PQ-style independent
assignment unless the caller passes codes) is computed outside and
streamed in with the x tile.

The batched-jnp fallback (``core/encode.py::icm_encode`` backend
dispatch) runs the identical residual recurrence in the identical
order, so jnp and pallas produce the same codes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.stages import pad_to

# Both ICM backends score at full f32 precision: the TPU's default f32
# dot rounds operands to bfloat16, which moves near-tie argmins, and the
# two backends only assign the same codes if they score alike.
ICM_PRECISION = jax.lax.Precision.HIGHEST


def _onehot(idx, m: int, dtype):
    """(blk_n,) int32 -> (blk_n, m) one-hot; matmul-gather helper."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, m), 1)
    return (idx[:, None] == iota).astype(dtype)


def _gather_rows(idx, Ck):
    """Codeword rows ``Ck[idx]`` as a one-hot matmul; exact at HIGHEST
    precision (one selected f32 row, the rest zeros)."""
    return jnp.dot(_onehot(idx, Ck.shape[0], Ck.dtype), Ck,
                   precision=ICM_PRECISION)


def _icm_kernel(x_ref, codes0_ref, c_ref, sq_ref, out_ref, *,
                K: int, m: int, iters: int):
    x = x_ref[...]                               # (blk_n, d) f32
    codes = codes0_ref[...].astype(jnp.int32)    # (blk_n, K) warm start
    C = c_ref[...]                               # (K, m, d) VMEM-pinned
    sq = sq_ref[...]                             # (K, m)
    col = jax.lax.broadcasted_iota(jnp.int32, codes.shape, 1)

    recon = jnp.zeros_like(x)
    for k in range(K):                           # static K: unrolled
        recon = recon + _gather_rows(codes[:, k], C[k])

    def sweep(_, carry):
        codes, recon = carry
        for k in range(K):
            r = recon - _gather_rows(codes[:, k], C[k])
            scores = sq[k][None, :] - 2.0 * jax.lax.dot_general(
                x - r, C[k], (((1,), (1,)), ((), ())),
                precision=ICM_PRECISION)
            new = jnp.argmin(scores, axis=-1).astype(jnp.int32)
            # column k <- new as a select (Mosaic has no scatter)
            codes = jnp.where(col == k, new[:, None], codes)
            recon = r + _gather_rows(new, C[k])
        return codes, recon

    codes, _ = jax.lax.fori_loop(0, iters, sweep, (codes, recon))
    out_ref[...] = codes


@functools.partial(jax.jit,
                   static_argnames=("iters", "block_n", "interpret"))
def icm_encode_pallas(x, init_codes, C, *, iters: int = 3,
                      block_n: int = 1024, interpret: bool = True):
    """Point-tiled ICM encode.  x (n, d) f32, init_codes (n, K) int
    (the warm start — PQ assignment or previous codes), C (K, m, d) f32
    -> codes (n, K) int32.

    Padding: n is zero-padded up to the (block_n,) grid; pad rows carry
    x = 0 / codes = 0 through the sweeps and are sliced off before
    returning (a zero point just argmins real scores — never NaN)."""
    from repro.core import codebooks as cb

    n, d = x.shape
    K, m, _ = C.shape
    n_pad = pl.cdiv(n, block_n) * block_n
    xp = pad_to(x.astype(jnp.float32), n_pad)
    cp = pad_to(init_codes.astype(jnp.int32), n_pad)
    sq = cb.codeword_sq_norms(C).astype(jnp.float32)
    out = pl.pallas_call(
        functools.partial(_icm_kernel, K=K, m=m, iters=iters),
        out_shape=jax.ShapeDtypeStruct((n_pad, K), jnp.int32),
        grid=(n_pad // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((block_n, K), lambda i: (i, 0)),
            pl.BlockSpec((K, m, d), lambda i: (0, 0, 0)),   # pinned
            pl.BlockSpec((K, m), lambda i: (0, 0)),          # pinned
        ],
        out_specs=pl.BlockSpec((block_n, K), lambda i: (i, 0)),
        interpret=interpret,
    )(xp, cp, C.astype(jnp.float32), sq)
    return out[:n]
