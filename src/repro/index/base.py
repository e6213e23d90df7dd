"""Index-layer foundations: the ``Index`` protocol, the shared
``SearchResult`` record, ADC LUT primitives, backend resolution, query
chunking, exact ground truth, and retrieval metrics (DESIGN.md §7).

Every concrete index (``flat.FlatADC``, ``flat.TwoStep``,
``ivf.IVFTwoStep``) speaks the same three-verb protocol:

    build(...)            -> Index      classmethod constructor
    search(queries, topk) -> SearchResult
    shard(mesh)           -> Index      mesh-sharded serving clone

so serving entries (``quant/serve_icq.build_ann_engine``,
``launch/serve.py --ann``) select an index kind by name and never touch
engine internals.  All implementations route through the same
``jnp | pallas | auto`` backend dispatch.

The ADC math lives here (moved from ``core/search.py``, which is now a
thin re-export): per-query LUTs ``T[k, j] = ||c_{k,j}||^2 - 2 <q,
c_{k,j}>`` and their masked sums — ranking by the LUT sum is ranking by
L2 distance after ICQ's hard projection (cross terms constant).

Quantized LUTs (DESIGN.md §8): ``quantize_lut`` calibrates a per-query
affine int8 form of the tables (Bolt / Quick-ADC style) and ``lut_sum``
accumulates the int8 entries in a narrow integer dtype before one
rescale back to true-distance units — the crude pass of the two-step
engines runs on these when ``lut_dtype="int8"``; the refine pass always
stays float32.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

LUT_DTYPES = ("f32", "int8")
CODE_BITS = (8, 4)


class SearchResult(NamedTuple):
    indices: jnp.ndarray     # (nq, topk) database ids, nearest first
    distances: jnp.ndarray   # (nq, topk) LUT-sum distances (monotone in L2)
    avg_ops: jnp.ndarray     # scalar — average LUT adds per database point
    pass_rate: jnp.ndarray   # scalar — fraction refined (phase-2 survivors)
    # Host-side resilience metadata (repro.resilience.budget.ResultMeta),
    # attached by the serving engine *outside* jit; None inside traced
    # index code (an empty pytree leaf-wise, so jit treats it as static).
    meta: Optional[object] = None


@runtime_checkable
class Index(Protocol):
    """The unified index protocol (DESIGN.md §7, §9).

    ``add`` is the incremental build surface: new vectors are encoded
    through the tiled engine (``core.encode.icm_encode``, PQ
    warm-started — exact for orthogonal-support codebooks too) and
    appended as rows (flat/two-step) or routed into the owning inverted
    lists (IVF) *without retraining*; a new index is returned (indexes
    are frozen).  Encoding is per-point, so an ``add`` produces search
    results identical to a from-scratch build over the concatenated
    data against the same codebooks (and, for IVF, the same coarse
    centroids)."""

    def search(self, queries, topk: Optional[int] = None) -> SearchResult:
        ...

    def add(self, new_vectors, *, icm_iters: int = 3) -> "Index":
        ...

    def shard(self, mesh) -> "Index":
        ...


# ----------------------------------------------------------------- LUTs ----

class QuantizedLUT(NamedTuple):
    """Per-query affine-int8 ADC tables (DESIGN.md §8).

    An f32 table ``T`` is calibrated per query from its min/max over the
    *summed* codebook subset: ``scale = (hi - lo) / 255``, and each
    entry is stored as ``q = round((T - lo) / scale) - 128`` in int8.
    Dequantization of a single entry is ``scale * q + bias`` with
    ``bias = lo + 128 * scale``; a sum over S selected entries is
    recovered *exactly in the bias term* as

        sum_T ~= scale * sum_q + S * bias

    so quantized crude distances stay in true-distance units and remain
    comparable against eq. 2 thresholds and across shards (the scale is
    query-global: it depends only on the query's LUT, never on which
    rows/lists a shard owns).

    Fields:
      q      int8 tables, same shape as the source LUT ((nq, K, m) or
             (K, m)); codebooks outside the calibration mask are zeroed
             so they contribute nothing to integer sums.
      scale  (nq,) (or scalar) f32 per-query step size, >= 1e-12.
      bias   (nq,) (or scalar) f32 per-*selected-entry* dequant offset.
    """
    q: jnp.ndarray
    scale: jnp.ndarray
    bias: jnp.ndarray


def resolve_lut_dtype(lut_dtype: str) -> str:
    """Validate the ``lut_dtype`` engine option ("f32" | "int8")."""
    if lut_dtype not in LUT_DTYPES:
        raise ValueError(f"unknown lut_dtype {lut_dtype!r}; "
                         f"expected one of {LUT_DTYPES}")
    return lut_dtype


def resolve_code_bits(code_bits) -> int:
    """Validate the ``code_bits`` storage option (8 | 4, DESIGN.md §12)."""
    if code_bits not in CODE_BITS:
        raise ValueError(f"unknown code_bits {code_bits!r}; "
                         f"expected one of {CODE_BITS}")
    return code_bits


def quantize_lut(lut, cb_mask=None) -> QuantizedLUT:
    """Per-query affine int8 calibration of ADC tables (DESIGN.md §8).

    lut:      (nq, K, m) or (K, m) f32 tables from ``build_lut``.
    cb_mask:  optional (K,) bool — calibrate min/max over (and keep
              only) this codebook subset; entries of masked-out
              codebooks are zeroed in the int8 table.  Pass the fast
              mask when the quantized table feeds a crude (fast-group)
              sum: the tighter range roughly halves the step size.

    Returns a ``QuantizedLUT``; the worst-case round-trip error of any
    kept entry is ``scale / 2`` (plus float rounding), so a sum over S
    entries is within ``S * scale / 2`` of the f32 sum.
    """
    red = tuple(range(lut.ndim - 2, lut.ndim))               # (K, m) axes
    if cb_mask is None:
        lo = jnp.min(lut, axis=red)
        hi = jnp.max(lut, axis=red)
    else:
        keep = cb_mask[:, None]                              # (K, 1)
        lo = jnp.min(jnp.where(keep, lut, jnp.inf), axis=red)
        hi = jnp.max(jnp.where(keep, lut, -jnp.inf), axis=red)
    scale = jnp.maximum((hi - lo) / 255.0, 1e-12)
    lo_b = lo[..., None, None]
    q = jnp.clip(jnp.round((lut - lo_b) / scale[..., None, None]) - 128.0,
                 -128.0, 127.0).astype(jnp.int8)
    if cb_mask is not None:
        q = q * cb_mask[:, None].astype(jnp.int8)
    return QuantizedLUT(q=q, scale=scale, bias=lo + 128.0 * scale)


def _bias_count(K: int, cb_mask):
    """Number of codebooks entering a quantized sum — the ``S`` of the
    accumulated-bias correction ``S * bias`` (DESIGN.md §8)."""
    return (jnp.asarray(float(K), jnp.float32) if cb_mask is None
            else jnp.sum(cb_mask.astype(jnp.float32)))


def dequantize_acc(qlut: QuantizedLUT, acc, cb_mask=None):
    """Rescale an integer LUT-sum accumulator to true-distance f32:
    ``scale * acc + count * bias`` — THE definition of the quantized
    dequant, shared by every jnp engine (``lut_sum``'s quantized body
    and the unrolled IVF loop); the fused kernels receive the identical
    (scale, offset) pair via ``quantized_kernel_operands`` and apply
    the same expression in the same order, which is what makes jnp /
    pallas / sharded int8 rankings bitwise-identical.

    acc: integer array whose *leading* dims broadcast against
    ``qlut.scale`` (e.g. (nq, n) acc with (nq,) scale, or (n,) acc
    with scalar scale)."""
    offset = _bias_count(qlut.q.shape[-2], cb_mask) * qlut.bias
    return (qlut.scale[..., None] * acc.astype(jnp.float32)
            + offset[..., None])


def quantized_kernel_operands(luts, cb_mask=None):
    """Calibrate ``luts`` ((nq, K, m) f32) and flatten into the fused
    crude kernels' operand triple: ``(q_flat (nq, K*m) int8, scale
    (nq,) f32, offset (nq,) f32)`` with ``offset = count * bias`` —
    the same accounting as ``dequantize_acc``."""
    qlut = quantize_lut(luts, cb_mask)
    nq, K, m = qlut.q.shape
    return (qlut.q.reshape(nq, K * m), qlut.scale,
            _bias_count(K, cb_mask) * qlut.bias)


def _int_acc_dtype(K: int):
    # |q| <= 128 per entry, so a K-codebook sum fits int16 whenever
    # K * 128 <= int16 max — true for every real config (K <= 255); the
    # narrow accumulator is the point of the quantized crude pass
    # (~half the accumulator traffic of f32/int32 on the CPU backend)
    return jnp.int16 if K * 128 <= jnp.iinfo(jnp.int16).max else jnp.int32


def build_lut(q, C):
    """Per-query ADC tables ``T[k, j] = ||c_{k,j}||^2 - 2 <q, c_{k,j}>``.

    q: (d,) or (nq, d) f32 queries; C: (K, m, d) codebooks ->
    (K, m) or (nq, K, m) f32.  Ranking by sums of these tables is
    ranking by L2 distance (the ``||q||^2`` term is constant per query).
    """
    # lazy: repro.core re-exports this module's names, so a module-level
    # import here would cycle when repro.index is imported first
    from repro.core import codebooks as cb
    sq = cb.codeword_sq_norms(C)                             # (K,m)
    hi = jax.lax.Precision.HIGHEST      # f32 tables, not bf16 ones, on TPU
    if q.ndim == 1:
        return sq - 2.0 * jnp.einsum("d,kmd->km", q, C, precision=hi)
    return sq[None] - 2.0 * jnp.einsum("qd,kmd->qkm", q, C, precision=hi)


def lut_sum(lut, codes, cb_mask=None):
    """Sum selected LUT entries — one vectorized ``take_along_axis``
    gather (vmap/batch friendly; no Python loop over codebooks).

    Shapes (f32 ``lut`` array or ``QuantizedLUT`` whose ``q`` has the
    same shape):
      lut (K,m),    codes (n,K)     -> (n,)
      lut (nq,K,m), codes (n,K)     -> (nq, n)   shared database codes
      lut (nq,K,m), codes (nq,t,K)  -> (nq, t)   per-query candidate codes

    ``codes`` may arrive in any integer dtype (packed uint8 included);
    they are widened to int32 gather indices here.

    ``cb_mask``: optional (K,) bool — restrict to a codebook subset
    (the fast group for crude distances).

    Passing a ``QuantizedLUT`` (from ``quantize_lut``) accumulates the
    int8 entries in the narrowest exact integer dtype (int16 for
    K <= 255, else int32) and applies one affine rescale at the end:
    ``scale * acc + count * bias`` with ``count`` the number of summed
    codebooks — the result is in true-distance units (DESIGN.md §8).
    The mask the table was *calibrated* with must cover the mask summed
    over here (masked-out codebooks are zeroed in ``q``, so the integer
    sum skips them but ``count`` must only count kept ones).
    """
    if isinstance(lut, QuantizedLUT):
        return _lut_sum_quantized(lut, codes, cb_mask)
    codes = codes.astype(jnp.int32)
    if cb_mask is not None:
        lut = lut * cb_mask[:, None].astype(lut.dtype)
    if lut.ndim == 3 and codes.ndim == 2:
        # batched LUTs against the shared database codes: accumulate one
        # (nq, n) gather per codebook (lax.scan over K) instead of
        # materializing the (nq, K, n) gather, which blows the cache at
        # serving sizes (~4x slower measured at nq=64, n=100k)
        def step(acc, lut_and_codes):
            lut_k, codes_k = lut_and_codes               # (nq,m), (n,)
            return acc + jnp.take(lut_k, codes_k, axis=1), None
        acc0 = jnp.zeros((lut.shape[0], codes.shape[0]), lut.dtype)
        acc, _ = jax.lax.scan(step, acc0,
                              (jnp.swapaxes(lut, 0, 1), codes.T))
        return acc
    idx = jnp.swapaxes(codes, -1, -2)                        # (..., K, n)
    parts = jnp.take_along_axis(lut, idx, axis=-1)           # (..., K, n)
    return jnp.sum(parts, axis=-2)


def _lut_sum_quantized(qlut: QuantizedLUT, codes, cb_mask=None):
    """Integer-accumulating ``lut_sum`` body for ``QuantizedLUT``s.

    Masked-out codebooks are already zeroed in ``qlut.q`` (quantize_lut
    calibration mask), so the integer accumulation simply sums all K
    gathered entries; ``cb_mask`` only determines the bias count.  The
    final rescale ``scale * acc + (count * bias)`` is ordered exactly
    like the fused kernels' dequant so jnp and pallas agree bitwise.
    """
    q = qlut.q
    acc_dt = _int_acc_dtype(q.shape[-2])
    codes = codes.astype(jnp.int32)
    if q.ndim == 3 and codes.ndim == 2:
        def step(acc, q_and_codes):
            q_k, codes_k = q_and_codes                   # (nq,m), (n,)
            return acc + jnp.take(q_k, codes_k, axis=1).astype(acc_dt), None
        acc0 = jnp.zeros((q.shape[0], codes.shape[0]), acc_dt)
        acc, _ = jax.lax.scan(step, acc0,
                              (jnp.swapaxes(q, 0, 1), codes.T))
        return dequantize_acc(qlut, acc, cb_mask)
    idx = jnp.swapaxes(codes, -1, -2)                        # (..., K, n)
    parts = jnp.take_along_axis(q, idx, axis=-1)             # (..., K, n)
    acc = jnp.sum(parts.astype(acc_dt), axis=-2)
    return dequantize_acc(qlut, acc, cb_mask)


def pad_luts_even(luts):
    """Zero-pad the codebook axis of ``luts`` ((..., K, m) f32 or int8)
    to even K — the sentinel codebook of the nibble format (DESIGN.md
    §12).  Its entries are all zero, so a sentinel nibble (always code
    0) contributes nothing to any sum; bias/offset accounting keeps
    counting the *real* codebooks only."""
    K = luts.shape[-2]
    if K % 2 == 0:
        return luts
    pad = [(0, 0)] * (luts.ndim - 2) + [(0, 1), (0, 0)]
    return jnp.pad(luts, pad)


def fastscan_kernel_operands(luts, cb_mask=None):
    """Calibrate ``luts`` ((nq, K, m) f32, m <= 16) into the fast-scan
    kernels' operand triple: ``(q_flat (nq, Keven*m) int8, scale (nq,),
    offset (nq,))`` where Keven = K rounded up to even with an all-zero
    sentinel codebook.  scale/offset are identical to
    ``quantized_kernel_operands`` (the sentinel never enters the bias
    count), so the dequant expression — and therefore the ranking —
    matches the 8-bit int8 path bitwise."""
    qlut = quantize_lut(luts, cb_mask)
    nq, K, m = qlut.q.shape
    q_pad = pad_luts_even(qlut.q)
    return (q_pad.reshape(nq, -1), qlut.scale,
            _bias_count(K, cb_mask) * qlut.bias)


def nibble_lut_sum(lut, packed, K: int, cb_mask=None):
    """``lut_sum`` over nibble-packed codes (``code_bits=4``,
    DESIGN.md §12).

    packed: (n, ceil(K/2)) or (nq, t, ceil(K/2)) uint8 from
    ``pack_nibbles``; K is the real codebook count (the sentinel column
    of odd K never contributes).

    f32 ``lut``: unpack and defer to ``lut_sum`` — values identical to
    the 8-bit path.  ``QuantizedLUT`` with shared database codes: the
    fast path — a per-query *paired-byte* table ``pair[kp, b] =
    q[2kp, b & 15] + q[2kp+1, b >> 4]`` ((nq, ceil(K/2), 256) int16,
    exact: two int8 entries always fit int16) turns the K-gather scan
    into a ceil(K/2)-gather scan directly over the packed bytes.  The
    integer accumulator equals the unpack-then-``lut_sum`` accumulator
    term for term, and the final ``dequantize_acc`` rescale is the same
    expression in the same order, so jnp / pallas / sharded rankings
    stay bitwise-identical across code_bits.
    """
    from repro.core.encode import unpack_nibbles
    if not isinstance(lut, QuantizedLUT):
        return lut_sum(lut, unpack_nibbles(packed, K), cb_mask)
    q = lut.q
    if q.ndim != 3 or packed.ndim != 2:
        # per-query candidate codes (small t) or single-query tables:
        # the widened path is already cheap there
        return _lut_sum_quantized(lut, unpack_nibbles(packed, K), cb_mask)
    nq, Kq, m = q.shape
    if Kq != K:
        raise ValueError(f"nibble_lut_sum: table has {Kq} codebooks, "
                         f"got K={K}")
    if m > 16:
        raise ValueError(f"nibble_lut_sum needs m <= 16 codewords "
                         f"(4-bit codes), got m={m}")
    q_pad = pad_luts_even(q)
    if m < 16:
        # pad the codeword axis to 16 so every nibble value indexes
        # in-range (codes < m, so pad entries are never selected)
        q_pad = jnp.pad(q_pad, ((0, 0), (0, 0), (0, 16 - m)))
    lo_q = q_pad[:, 0::2, :].astype(jnp.int16)           # (nq, Kp, 16)
    hi_q = q_pad[:, 1::2, :].astype(jnp.int16)
    pair = (hi_q[:, :, :, None]
            + lo_q[:, :, None, :]).reshape(nq, -1, 256)  # (nq, Kp, 256)
    acc_dt = _int_acc_dtype(K)
    codes = packed.astype(jnp.int32)

    def step(acc, pair_and_codes):
        pair_kp, codes_kp = pair_and_codes               # (nq,256), (n,)
        return acc + jnp.take(pair_kp, codes_kp,
                              axis=1).astype(acc_dt), None

    acc0 = jnp.zeros((nq, codes.shape[0]), acc_dt)
    acc, _ = jax.lax.scan(step, acc0,
                          (jnp.swapaxes(pair, 0, 1), codes.T))
    return dequantize_acc(lut, acc, cb_mask)


# ------------------------------------------------------------- dispatch ----

def resolve_backend(backend: str) -> str:
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    if backend not in ("jnp", "pallas"):
        raise ValueError(f"unknown search backend {backend!r}")
    return backend


def chunked_over_queries(fn, queries, query_chunk: Optional[int]):
    """Apply the vectorized ``fn`` to query blocks of ``query_chunk`` (a
    working-set bound for huge batches); None = one block.

    queries: (nq, d).  When nq is not a multiple of ``query_chunk`` the
    batch is zero-padded up to the next multiple, ``fn`` runs on every
    (query_chunk, d) block via ``lax.map``, and every output leaf is
    sliced back to its true first-``nq`` rows — callers never see pad
    queries, but ``fn`` must tolerate all-zero query rows (every engine
    here does: a zero query just produces finite distances that are
    discarded by the slice).
    """
    from repro.kernels.stages import pad_to
    if query_chunk is None or queries.shape[0] <= query_chunk:
        return fn(queries)
    nq = queries.shape[0]
    qp = pad_to(queries, nq + (-nq) % query_chunk)
    blocks = qp.reshape(-1, query_chunk, queries.shape[1])
    outs = jax.lax.map(fn, blocks)
    return jax.tree.map(
        lambda a: a.reshape((-1,) + a.shape[2:])[:nq], outs)


def as_filter(filter, n: int):
    """Validate a per-row metadata predicate: a length-``n`` boolean
    vector (True = row eligible).  Any array-like of shape (n,) is
    accepted and cast to bool; wrong shapes raise by name."""
    f = jnp.asarray(filter)
    if f.ndim != 1 or f.shape[0] != n:
        raise ValueError(f"filter must be a ({n},) boolean predicate "
                         f"(one entry per database row), got shape "
                         f"{tuple(f.shape)}")
    return f.astype(bool)


def mask_filtered_ids(ids, dist):
    """Post-filter result convention: slots whose distance is +inf (no
    eligible row left to fill them) report id ``-1``.  Applied only on
    filtered searches so unfiltered results stay bitwise unchanged."""
    return jnp.where(jnp.isinf(dist), -1, ids)


def exact_search(queries, X, topk: int, *,
                 query_chunk: Optional[int] = None, filter=None):
    """Brute-force L2 ground truth.  queries: (nq,d), X: (n,d).

    ``query_chunk`` bounds the dense (nq, n) distance matrix to
    (query_chunk, n) blocks — ground-truth computation at benchmark
    sizes (nq x n = 64 x 1M) OOMs without it.

    ``filter``: optional (n,) bool per-row predicate — rows where it is
    False are excluded (the filtered-search oracle).  When fewer than
    ``topk`` rows pass, the tail slots report id ``-1`` at distance
    ``+inf``.
    """
    xsq = jnp.sum(jnp.square(X), -1)[None, :]
    pred = None if filter is None else as_filter(filter, X.shape[0])

    def one_block(qs):
        d2 = (jnp.sum(jnp.square(qs), -1)[:, None]
              - 2.0 * jnp.dot(qs, X.T, precision=jax.lax.Precision.HIGHEST)
              + xsq)
        if pred is not None:
            d2 = jnp.where(pred[None, :], d2, jnp.inf)
        neg, idx = jax.lax.top_k(-d2, topk)
        if pred is not None:
            idx = mask_filtered_ids(idx, -neg)
        return idx, -neg

    return chunked_over_queries(one_block, queries, query_chunk)


# --------------------------------------------------------------- metrics ----

def mean_average_precision(retrieved_ids, db_labels, query_labels):
    """Label-based MAP (the paper's metric): a retrieved point is relevant
    iff it shares the query's class.  retrieved_ids: (nq, R)."""
    rel = (db_labels[retrieved_ids] == query_labels[:, None]).astype(jnp.float32)
    ranks = jnp.arange(1, rel.shape[1] + 1, dtype=jnp.float32)[None, :]
    cum = jnp.cumsum(rel, axis=1)
    prec_at = cum / ranks
    denom = jnp.maximum(jnp.sum(rel, axis=1), 1.0)
    ap = jnp.sum(prec_at * rel, axis=1) / denom
    return jnp.mean(ap)


def recall_at(retrieved_ids, true_ids):
    """Fraction of true nearest neighbors recovered.  Both (nq, R)."""
    hits = (retrieved_ids[:, :, None] == true_ids[:, None, :]).any(axis=1)
    return jnp.mean(hits.astype(jnp.float32))
