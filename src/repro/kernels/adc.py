"""Pallas TPU kernel: ADC LUT sum (asymmetric distance computation).

Given per-query LUTs T (K, m) and database codes (n, K), computes
dist_i = sum_k T[k, codes[i, k]] for a tile of points at a time.

TPU adaptation (DESIGN.md §3): the per-element table *gather* of the GPU
formulation maps poorly onto the VPU lanes; instead each tile does a
one-hot(codes) x LUT **matmul** on the MXU — onehot (blk_n, K*m) times
flattened LUT (K*m,) — which is dense, layout-friendly, and at m=256,
K<=16 still arithmetically cheap (2*K*m = 8K flops/point at 197 TFLOP/s
beats an HBM-bound gather).  The LUT (K*m*4B <= 16 KiB) is pinned in
VMEM across the whole grid; code tiles stream HBM->VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def flat_onehot(codes, K: int, m: int, dtype, books=None):
    """(blk_n, K) int codes -> (blk_n, len(books)*m) one-hot over the
    flattened LUT of the codebooks ``books`` (a static tuple of ids,
    default all K), one m-wide block per listed codebook in that order,
    with exactly one 1 per block.  The ids need not be contiguous.

    Each block is one compare of a code column against an m-wide iota,
    so the peak intermediate is the size of the result,
    O(blk_n * len(books) * m), and an unlisted codebook costs nothing.
    """
    books = range(K) if books is None else books
    iota = jax.lax.broadcasted_iota(jnp.int32, (codes.shape[0], m), 1)
    return jnp.concatenate(
        [(codes[:, b:b + 1] == iota).astype(dtype) for b in books], axis=1)


def _adc_kernel(codes_ref, lut_ref, out_ref, *, K: int, m: int):
    codes = codes_ref[...]                      # (blk_n, K) int32
    lut = lut_ref[...]                          # (K, m) f32
    onehot = flat_onehot(codes, K, m, lut.dtype)     # (blk_n, K*m)
    out_ref[...] = onehot @ lut.reshape(K * m)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def adc_pallas(codes, lut, *, block_n: int = 512, interpret: bool = True):
    """codes: (n, K) int; lut: (K, m) float32 -> dists (n,) float32."""
    n, K = codes.shape
    m = lut.shape[1]
    if n % block_n != 0:
        block_n = _largest_divisor(n, block_n)
    grid = (n // block_n,)
    return pl.pallas_call(
        functools.partial(_adc_kernel, K=K, m=m),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, K), lambda i: (i, 0)),
            pl.BlockSpec((K, m), lambda i: (0, 0)),   # LUT pinned in VMEM
        ],
        out_specs=pl.BlockSpec((block_n,), lambda i: (i,)),
        interpret=interpret,
    )(codes.astype(jnp.int32), lut.astype(jnp.float32))


def _largest_divisor(n: int, cap: int) -> int:
    for b in range(min(cap, n), 0, -1):
        if n % b == 0:
            return b
    return 1
