"""Encoding: map embeddings to discrete codes against the codebooks.

PQ encode is independent per codebook (orthogonal supports).  Additive
codes (CQ / ICQ) interact, so we use Iterated Conditional Modes (ICM):
cyclically re-choose codebook k's codeword holding the others fixed.

``icm_encode`` is the tiled encoding engine (DESIGN.md §9): it follows
the same ``jnp | pallas | auto`` backend dispatch as the search engines.
Both backends run the *residual* recurrence — carry the current
reconstruction, and per codebook k score

    argmin_j  ||c_{k,j}||^2 - 2 <x - r_k, c_{k,j}>,
    r_k = recon - c_{k, b_k}   (the others-only partial sum)

one (n, d) x (d, m) matmul per codebook, never materializing the
(K, K, m, m) cross-Gram or the (K, n, m) query tensor of the seed
formulation (kept as the oracle, ``kernels/ref.py::icm_encode_gram``);
``point_chunk`` bounds the jnp working set for database-sized inputs.
The interaction term <r, c_{k,j}> is exactly the summed Gram row, so
the per-step objective is identical and every sweep is non-increasing.

``soft_assign`` is the differentiable (softmax) relaxation used during
joint training, with straight-through hard codes for the forward pass.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import codebooks as cb
from repro.kernels.icm_encode import ICM_PRECISION


def encode_pq(x, C):
    """Independent per-codebook nearest codeword (exact for orthogonal
    supports).  x: (n,d), C: (K,m,d) -> (n,K) int32."""
    # scores[k]: (n, m) = -2 x C_k^T + ||c||^2, at full f32 precision so
    # the ICM warm start does not depend on how XLA fuses the dot
    sq = cb.codeword_sq_norms(C)                             # (K,m)
    scores = -2.0 * jnp.einsum("nd,kmd->knm", x, C,
                               precision=ICM_PRECISION) + sq[:, None, :]
    return jnp.argmin(scores, axis=-1).T.astype(jnp.int32)   # (n,K)


def _icm_block_jnp(x, C, sq, codes, iters: int):
    """Residual-formulation ICM sweeps over one point block.

    x (n, d) f32, codes (n, K) int32 warm start -> (n, K) int32.  The
    recurrence and operation order mirror the pallas kernel
    (``kernels/icm_encode.py``) exactly, so both backends assign the
    same codes."""
    recon = cb.decode(C, codes)                              # (n, d)

    def sweep(carry, _):
        def step(carry, k):
            codes, recon = carry
            Ck = C[k]                                        # (m, d)
            bk = jax.lax.dynamic_index_in_dim(codes, k, axis=1,
                                              keepdims=False)
            r = recon - jnp.take(Ck, bk, axis=0)
            scores = sq[k][None, :] - 2.0 * jnp.dot(        # (n, m)
                x - r, Ck.T, precision=ICM_PRECISION)
            new = jnp.argmin(scores, axis=-1).astype(jnp.int32)
            codes = jax.lax.dynamic_update_slice_in_dim(
                codes, new[:, None], k, axis=1)
            return (codes, r + jnp.take(Ck, new, axis=0)), None

        carry, _ = jax.lax.scan(step, carry, jnp.arange(C.shape[0]))
        return carry, None

    (codes, _), _ = jax.lax.scan(sweep, (codes, recon), None, length=iters)
    return codes


def icm_encode(x, C, iters: int = 3, init_codes=None, *,
               backend: str = "auto", point_chunk: Optional[int] = None,
               block_n: int = 1024, interpret=None):
    """ICM encoding for additive codebooks.  x: (n,d) -> codes (n,K)
    int32 (the tiled encoding engine, DESIGN.md §9).

    Warm-started from the independent (PQ-style) assignment unless
    ``init_codes`` given.  Each sweep visits codebooks in order;
    ``iters`` full sweeps (paper uses a small constant, cfg.icm_iters).

    backend:      "jnp" | "pallas" | "auto" (pallas on TPU) — the same
                  dispatch as the search engines; both backends run the
                  identical residual recurrence and assign identical
                  codes (``kernels/ref.py::icm_encode_gram`` is the
                  seed-formulation oracle).
    point_chunk:  optional working-set bound for the jnp engine and the
                  warm start: points are processed in zero-padded
                  blocks of this size via ``lax.map`` (pad rows sliced
                  off; encoding is per-point independent, so chunking
                  never changes a point's codes).
    block_n:      pallas point-tile size.
    interpret:    pallas interpret-mode override (defaults off-TPU).
    """
    from repro.index.base import resolve_backend

    be = resolve_backend(backend)
    n = x.shape[0]
    K = C.shape[0]
    sq = cb.codeword_sq_norms(C)

    def encode_block(args):
        xb, cb0 = args
        codes0 = encode_pq(xb, C) if init_codes is None else cb0
        if be == "pallas":
            from repro.kernels.icm_encode import icm_encode_pallas
            it = (jax.default_backend() != "tpu" if interpret is None
                  else interpret)
            return icm_encode_pallas(xb, codes0, C, iters=iters,
                                     block_n=block_n, interpret=it)
        return _icm_block_jnp(xb, C, sq, codes0, iters)

    codes0_all = (jnp.zeros((n, K), jnp.int32) if init_codes is None
                  else init_codes.astype(jnp.int32))
    if point_chunk is None or n <= point_chunk:
        return encode_block((x, codes0_all))
    pad = (-n) % point_chunk
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    cp = jnp.pad(codes0_all, ((0, pad), (0, 0)))
    blocks = (xp.reshape(-1, point_chunk, x.shape[1]),
              cp.reshape(-1, point_chunk, K))
    out = jax.lax.map(encode_block, blocks)
    return out.reshape(-1, K)[:n]


def soft_assign(x, C, tau: float = 1.0):
    """Differentiable assignment: softmax(-dist/tau) per codebook.

    Returns (probs (K,n,m), hard codes (n,K)).  The straight-through
    reconstruction is built in ``st_decode``.
    """
    sq = cb.codeword_sq_norms(C)
    scores = -2.0 * jnp.einsum("nd,kmd->knm", x, C) + sq[:, None, :]
    probs = jax.nn.softmax(-scores / tau, axis=-1)
    hard = jnp.argmin(scores, axis=-1).T.astype(jnp.int32)
    return probs, hard


def st_decode(x, C, tau: float = 1.0):
    """Straight-through decode: forward = hard reconstruction, backward =
    soft (differentiable wrt both x and C).  Returns (xbar, codes)."""
    probs, hard = soft_assign(x, C, tau)
    soft_rec = jnp.einsum("knm,kmd->nd", probs, C)
    hard_rec = cb.decode(C, hard)
    xbar = soft_rec + jax.lax.stop_gradient(hard_rec - soft_rec)
    return xbar, hard


def pack_codes(codes, m: int):
    """Compress int32 codes to the narrowest unsigned dtype that fits m
    (uint8 for m <= 256, uint16 for m <= 65536).  Both packed widths are
    accepted end-to-end by the search engines — codes widen to int32 at
    the LUT-sum / kernel boundary (``tests/test_trainer.py`` keeps the
    uint16 path covered)."""
    if m <= 256:
        return codes.astype(jnp.uint8)
    if m <= 65536:
        return codes.astype(jnp.uint16)
    return codes.astype(jnp.int32)


def unpack_codes(codes):
    return codes.astype(jnp.int32)


def pack_nibbles(codes, K: int):
    """Pack 4-bit codes two-per-byte along the codebook axis (the
    ``code_bits=4`` storage format, DESIGN.md §12).

    codes: (..., K) integer codes with every value < 16 -> (..., ceil(K/2))
    uint8 where byte kp holds codebook 2*kp in its low nibble and
    codebook 2*kp+1 in its high nibble.  Odd K is padded with one
    sentinel column (value 0) in the final byte's high nibble; the
    sentinel never reaches ``lut_sum`` — ``unpack_nibbles`` slices it
    off, and the fast-scan kernels give it an all-zero LUT column.

    The round trip ``unpack_nibbles(pack_nibbles(c, K), K) == c`` is
    exact for any valid codes, mirroring the uint8/uint16
    ``pack_codes``/``unpack_codes`` contract.
    """
    if K != codes.shape[-1]:
        raise ValueError(f"pack_nibbles: codes have {codes.shape[-1]} "
                         f"codebooks, got K={K}")
    c = codes.astype(jnp.int32)
    if K % 2:
        pad = [(0, 0)] * (c.ndim - 1) + [(0, 1)]
        c = jnp.pad(c, pad)                       # sentinel column = 0
    lo = c[..., 0::2]
    hi = c[..., 1::2]
    return (lo | (hi << 4)).astype(jnp.uint8)


def unpack_nibbles(packed, K: int):
    """Inverse of ``pack_nibbles``: (..., ceil(K/2)) uint8 -> (..., K)
    int32, dropping the sentinel column when K is odd."""
    p = packed.astype(jnp.int32)
    lo = p & 0xF
    hi = (p >> 4) & 0xF
    codes = jnp.stack([lo, hi], axis=-1).reshape(*p.shape[:-1],
                                                 2 * p.shape[-1])
    return codes[..., :K]
