"""Composable search-stage primitives with explicit buffer ownership
(DESIGN.md §13).

The two-step engines (flat and IVF, jnp and Pallas) are compositions of
three stages:

    CrudeStage      fast-subset LUT sums (+ the crude top-k on the fused
                    kernels) — the cheap pass of paper eq. 2.
    ThresholdStage  the eq. 2 threshold bootstrap: rank the crude top-k
                    candidates by full distance, take the furthest
                    element's crude value + sigma.
    RefineStage     slow-codebook sums for margin-test survivors and the
                    final full-distance top-k (eq. 1: full = crude +
                    slow).

Every monolithic search path in ``index/flat.py`` / ``index/ivf.py`` is
expressed as a composition of these objects, and the ``PipelinedSearch``
executor (``index/pipelined.py``) runs the same stages split at the
crude/refine boundary so the crude pass of query-tile t+1 overlaps the
refine of tile t.  The stages wrap the *existing* jnp bodies and fused
Pallas kernels unchanged — composition happens at the operand level, so
composed results are bitwise-identical to the historical monolithic
paths (tested in ``tests/test_stages.py``).

Buffer ownership (the contract the pipelined executor relies on):

  stage           borrows                          owns (produces)    donates
  CrudeStage      codes / candidate slab, LUT      crude, cand_vals,  —
                  tiles (flattened kernel           cand_idx (, slow)
                  operands), cand_ids, filter
  ThresholdStage  luts, codes/slab, crude or       thr                —
                  (cand_vals, cand_idx)
  RefineStage     codes/slab, slow LUT tiles,      dist, idx          crude
                  thr, safe ids                                       carry

"Borrows" are operands the stage reads but never invalidates — the
executor may alias them across tiles (database codes, codebooks, the
candidate slab).  "Owns" are buffers the stage allocates and hands to
its consumer.  "Donates" marks the inter-stage carry a consumer may
reuse in place: ``RefineStage`` is the last reader of the dense crude
matrix, so the pipelined executor jits the refine phase with
``donate_argnums`` on the carry and XLA recycles the (tile, n) buffer
for the next tile instead of allocating a fresh one.

This module is also the canonical home of the tile helpers that were
historically copy-pasted per kernel file: ``pad_to``, ``merge_topk`` /
``init_topk``, ``unpack_nibble_tile``, ``check_quantized_args``,
``resolve_kernel_code_bits``, ``widen_codes``.  ``batched_search.py``,
``icm_encode.py``, ``ops.py`` and ``index/base.py`` import them from
here.

Layering note: stage methods lazily import ``repro.kernels.ops`` and
``repro.index.base`` *inside* their bodies — ``batched_search.py``
imports this module's helpers at its top, so a module-level import of
``ops`` here would cycle.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

I32_MAX = jnp.iinfo(jnp.int32).max


# ------------------------------------------------------- shared helpers ----

def pad_to(x, rows: int):
    """The shared padding contract of every tiled kernel wrapper:
    zero-pad the *leading* axis of ``x`` up to ``rows`` (a whole number
    of grid tiles).  Pad rows are real kernel inputs — each kernel
    masks the pad columns/rows it produces to +inf (or carries validity
    ids) so padding never reaches a returned value; callers always
    slice outputs back to true sizes before returning."""
    return x if x.shape[0] == rows else jnp.pad(
        x, [(0, rows - x.shape[0])] + [(0, 0)] * (x.ndim - 1))


def _key_less(av, ai, bv, bi):
    """Two-key order on (distance, global index): the order in which
    ``top_k(-dist)`` returns entries (lowest index wins a tie)."""
    return (av < bv) | ((av == bv) & (ai < bi))


def _row_min_key(v, i):
    """Per-row smallest (distance, index) key -> two (rows, 1) columns."""
    mv = jnp.min(v, axis=1, keepdims=True)
    mi = jnp.min(jnp.where(v == mv, i, I32_MAX), axis=1, keepdims=True)
    return mv, mi


def merge_topk(vals_ref, idx_ref, tile_vals, tile_idx, topk: int):
    """Merge a (blk_q, blk_n) tile into the running (blk_q, topk) sets.

    The running set is kept unsorted; ``finish_topk`` orders it once
    after the last tile.  Each step moves the tile's best remaining key
    into the slot of the set's worst key while it beats that key, so
    the set ends as the ``topk`` smallest (distance, global index) keys
    seen — ``top_k(-dist)``'s selection with its lowest-index tie-break.
    The step count is the largest per-row number of tile keys that beat
    the row's worst key on entry (at most ``topk``): after the first
    tiles most rows admit nothing, and a whole tile costs one compare.
    Built from compares, min/max reductions and selects only, which
    Mosaic lowers (it has no sort)."""
    rv, ri = vals_ref[...], idx_ref[...]
    col = jax.lax.broadcasted_iota(jnp.int32, rv.shape, 1)
    wv = jnp.max(rv, axis=1, keepdims=True)
    wi = jnp.max(jnp.where(rv == wv, ri, -1), axis=1, keepdims=True)
    beats = _key_less(tile_vals, tile_idx, wv, wi).astype(jnp.int32)
    steps = jnp.minimum(jnp.max(jnp.sum(beats, axis=1, keepdims=True)),
                        topk)

    def step(_, carry):
        tv, ti, rv, ri = carry
        cv, ci = _row_min_key(tv, ti)
        wv = jnp.max(rv, axis=1, keepdims=True)
        wi = jnp.max(jnp.where(rv == wv, ri, -1), axis=1, keepdims=True)
        slot = jnp.min(jnp.where((rv == wv) & (ri == wi), col, topk),
                       axis=1, keepdims=True)
        take = _key_less(cv, ci, wv, wi)
        put = take & (col == slot)
        rv = jnp.where(put, cv, rv)
        ri = jnp.where(put, ci, ri)
        # a taken key becomes the (inf, id_max) filler no key loses to
        gone = take & (ti == ci)
        tv = jnp.where(gone, jnp.inf, tv)
        ti = jnp.where(gone, I32_MAX, ti)
        return tv, ti, rv, ri

    _, _, rv, ri = jax.lax.fori_loop(0, steps, step,
                                     (tile_vals, tile_idx, rv, ri))
    vals_ref[...] = rv
    idx_ref[...] = ri


def finish_topk(vals_ref, idx_ref):
    """Order the running sets ascending by (distance, global index) in
    place — a selection sort of ``topk`` steps, run once per query tile
    after its last point tile."""
    rv, ri = vals_ref[...], idx_ref[...]
    col = jax.lax.broadcasted_iota(jnp.int32, rv.shape, 1)
    topk = rv.shape[1]

    def step(j, carry):
        rv, ri, ov, oi = carry
        mv, mi = _row_min_key(rv, ri)
        slot = jnp.min(jnp.where((rv == mv) & (ri == mi), col, topk),
                       axis=1, keepdims=True)
        out = col == j
        ov = jnp.where(out, mv, ov)
        oi = jnp.where(out, mi, oi)
        gone = col == slot
        rv = jnp.where(gone, jnp.inf, rv)
        ri = jnp.where(gone, I32_MAX, ri)
        return rv, ri, ov, oi

    _, _, ov, oi = jax.lax.fori_loop(0, topk, step, (rv, ri, rv, ri))
    vals_ref[...] = ov
    idx_ref[...] = oi


def init_topk(vals_ref, idx_ref):
    """Seed the running top-k carry: +inf distances, id_max indices —
    the all-ties tail every real candidate sorts ahead of."""
    vals_ref[...] = jnp.full(vals_ref.shape, jnp.inf, jnp.float32)
    idx_ref[...] = jnp.full(idx_ref.shape, I32_MAX, jnp.int32)


def unpack_nibble_tile(packed):
    """In-VMEM shift/mask unpack of a nibble-packed codes tile
    (DESIGN.md §12): (..., Kp) int32 bytes -> (..., 2*Kp) int32 codes,
    byte kp -> (low nibble, high nibble) = codebooks (2kp, 2kp+1).  The
    sentinel column of odd K stays in place — its LUT column is all
    zero (``index.base.pad_luts_even``), so it adds nothing to any
    dot."""
    lo = packed & 0xF
    hi = (packed >> 4) & 0xF
    return jnp.stack([lo, hi], axis=-1).reshape(*packed.shape[:-1], -1)


def resolve_kernel_code_bits(code_bits: int, Kc: int, Km: int):
    """Shared wrapper-side geometry: the stored code columns ``Kc``
    widen to ``K = 2 * Kc`` codebook columns under the nibble format
    (``code_bits=4``); the flattened LUT width ``Km`` must then be an
    even-K multiple (sentinel codebook included)."""
    if code_bits not in (8, 4):
        raise ValueError(f"unknown code_bits {code_bits!r}; "
                         f"expected one of (8, 4)")
    K = 2 * Kc if code_bits == 4 else Kc
    if Km % K:
        raise ValueError(
            f"lut_flat width {Km} is not a multiple of K={K}"
            + (" (pad odd-K tables with index.base.pad_luts_even)"
               if code_bits == 4 else ""))
    return K, Km // K


def check_quantized_args(lut_flat, lut_scale, lut_offset) -> bool:
    """int8 LUTs need the per-query affine columns; f32 forbids them."""
    if lut_flat.dtype == jnp.int8:
        if lut_scale is None or lut_offset is None:
            raise ValueError("int8 lut_flat requires lut_scale and "
                             "lut_offset (see index.base.quantize_lut)")
        return True
    if lut_scale is not None or lut_offset is not None:
        raise ValueError("lut_scale/lut_offset are only valid with an "
                         "int8 lut_flat")
    return False


def widen_codes(codes, K: int, code_bits: int):
    """Stored codes (any trailing-axis-packed gather) -> int32 codebook
    indices: plain widening for byte codes, shift/mask nibble unpack
    (sentinel column dropped) for ``code_bits=4``.  Works on (n, Kc)
    rows and (nq, t, Kc) gathered slabs alike."""
    if code_bits == 4:
        from repro.core.encode import unpack_nibbles
        return unpack_nibbles(codes, K)
    return codes.astype(jnp.int32)


# ---------------------------------------------------- kernel LUT operands ----

def crude_lut_operands(luts, fast=None, *, quantized: bool,
                       code_bits: int = 8):
    """The crude pass's flattened kernel operand triple ``(lut_flat,
    lut_scale, lut_offset)`` from per-query tables ``luts`` ((nq, K, m)
    f32) and the optional fast mask — the branch every Pallas search
    path used to inline.  f32 mode masks the tables and returns
    ``(flat, None, None)``; int8 mode calibrates the per-query affine
    (``quantized_kernel_operands`` / even-K ``fastscan_kernel_operands``
    under the nibble format)."""
    from repro.index.base import (fastscan_kernel_operands, pad_luts_even,
                                  quantized_kernel_operands)
    nibble = code_bits == 4
    if quantized:
        return (fastscan_kernel_operands(luts, fast) if nibble
                else quantized_kernel_operands(luts, fast))
    if fast is None:
        lut = luts
    else:
        fast_f = fast.astype(luts.dtype)[None, :, None]
        lut = luts * fast_f
    lut = pad_luts_even(lut) if nibble else lut
    return lut.reshape(luts.shape[0], -1), None, None


def slow_lut_operand(luts, fast, *, code_bits: int = 8):
    """The refine pass's flattened slow-masked f32 tables (the refine
    pass is never quantized — eq. 2's exact re-ranking)."""
    from repro.index.base import pad_luts_even
    fast_f = fast.astype(luts.dtype)[None, :, None]
    lut_slow = luts * (1.0 - fast_f)
    lut_slow = (pad_luts_even(lut_slow) if code_bits == 4
                else lut_slow).reshape(luts.shape[0], -1)
    return lut_slow


# -------------------------------------------------------- stage protocol ----

class BufferSpec(NamedTuple):
    """A stage's operand contract: ``borrows`` are read-only inputs the
    executor may alias across tiles, ``owns`` are buffers the stage
    allocates for its consumer, ``donates`` names the inter-stage carry
    this stage is the last reader of (safe for ``jax.jit``
    ``donate_argnums`` reuse)."""
    borrows: Tuple[str, ...]
    owns: Tuple[str, ...]
    donates: Tuple[str, ...] = ()


class CrudeOut(NamedTuple):
    """CrudeStage products.  ``crude`` is the dense (nq, n|nc) matrix
    (None when ``want_crude=False``); ``cand_vals``/``cand_idx`` are the
    fused kernels' running crude top-k (None on the dense jnp paths,
    which defer the top-k to the threshold bootstrap); ``slow`` is the
    jnp IVF engine's fused slow accumulator (its unrolled slab sweep
    feeds both sums in one pass — the stage owns both buffers)."""
    crude: Optional[jnp.ndarray]
    cand_vals: Optional[jnp.ndarray] = None
    cand_idx: Optional[jnp.ndarray] = None
    slow: Optional[jnp.ndarray] = None


@dataclasses.dataclass(frozen=True)
class CrudeStage:
    """Phase 1 of eq. 2: fast-subset crude distances.

    Static config only — traced operands go through ``__call__``
    (flat: shared database codes) / ``slab`` (IVF: gathered candidate
    slab).  ``backend="pallas"`` wraps the fused crude kernels
    (``ops.batched_crude_topk`` / ``ops.ivf_crude_topk``), which also
    emit the running crude top-k; ``backend="jnp"`` produces the dense
    crude matrix via the vectorized LUT sums."""
    backend: str = "jnp"                # "jnp" | "pallas"
    topk: int = 50
    block_q: int = 64
    block_n: int = 512
    interpret: Optional[bool] = None
    quantized: bool = False
    code_bits: int = 8
    want_crude: bool = True

    buffers = BufferSpec(
        borrows=("codes | cand_codes", "luts", "cand_ids", "filter pred"),
        owns=("crude", "cand_vals", "cand_idx", "slow (ivf jnp)"))

    def __call__(self, codes, luts, fast=None, *, pred=None) -> CrudeOut:
        """Flat crude pass.  codes (n, K) packed (nibble rows under
        ``code_bits=4``), luts (nq, K, m) f32, fast optional (K,) bool
        (None = full-table one-step ADC), pred optional (n,) bool
        filter (jnp only — excluded rows score +inf)."""
        nibble = self.code_bits == 4
        if self.backend == "pallas":
            from repro.kernels import ops
            lut_flat, scale, offset = crude_lut_operands(
                luts, fast, quantized=self.quantized,
                code_bits=self.code_bits)
            crude, vals, idx = ops.batched_crude_topk(
                codes, lut_flat, self.topk, block_q=self.block_q,
                block_n=self.block_n, interpret=self.interpret,
                want_crude=self.want_crude, lut_scale=scale,
                lut_offset=offset, code_bits=self.code_bits)
            return CrudeOut(crude, vals, idx)
        from repro.index.base import (lut_sum, nibble_lut_sum,
                                      quantize_lut)
        K = luts.shape[1]
        ct = quantize_lut(luts, fast) if self.quantized else luts
        crude = (nibble_lut_sum(ct, codes, K, fast) if nibble
                 else lut_sum(ct, codes, fast))
        if pred is not None:
            crude = jnp.where(pred[None, :], crude, jnp.inf)
        return CrudeOut(crude)

    def slab(self, cand_codes, cand_ids, valid, luts, fast, *,
             need_slow: bool = False) -> CrudeOut:
        """IVF crude pass over the gathered candidate slab.  cand_codes
        (nq, nc, Kc) packed, cand_ids (nq, nc) global ids (-1 pad),
        valid (nq, nc) bool (ids >= 0, possibly anded with a filter
        predicate — the jnp engine's exclusion channel).

        jnp: one unrolled sweep over the K codebooks feeds the crude
        (and, with ``need_slow``, the slow) accumulator — the stage
        owns both buffers; splitting the sweep would double the slab
        gathers.  pallas: the fused slab kernel, which inherits
        validity through the +inf-masked dense crude output."""
        if self.backend == "pallas":
            from repro.kernels import ops
            lut_flat, scale, offset = crude_lut_operands(
                luts, fast, quantized=self.quantized,
                code_bits=self.code_bits)
            crude, vals, pos = ops.ivf_crude_topk(
                cand_codes, cand_ids, lut_flat, self.topk,
                block_q=self.block_q, block_n=self.block_n,
                interpret=self.interpret, lut_scale=scale,
                lut_offset=offset, code_bits=self.code_bits)
            return CrudeOut(crude, vals, pos)
        from repro.index.ivf import _ivf_crude_scores
        crude, slow = _ivf_crude_scores(luts, cand_codes, valid, fast,
                                        quantized=self.quantized,
                                        need_slow=need_slow,
                                        code_bits=self.code_bits)
        return CrudeOut(crude, slow=slow)


@dataclasses.dataclass(frozen=True)
class ThresholdStage:
    """The eq. 2 threshold bootstrap: the neighbor list is the crude
    top-k; its furthest element (by full distance) sets ``thr = t +
    sigma``.  Tiny — (nq, topk) work — and always jnp, even between the
    fused kernels.

    ``quantized`` selects the decomposed full-distance form
    (quantized-crude + exact-slow) that keeps jnp and Pallas thresholds
    bitwise-identical under ``lut_dtype="int8"``; the dense f32 jnp
    path ranks candidates by one full-table sum instead (the historical
    formulation — preserved exactly)."""
    topk: int = 50
    quantized: bool = False
    code_bits: int = 8

    buffers = BufferSpec(
        borrows=("luts", "codes | cand_codes",
                 "crude | (cand_vals, cand_idx)"),
        owns=("thr",))

    def from_dense(self, luts, codes, crude, fast, sigma):
        """Bootstrap from the dense crude matrix (jnp flat path):
        exactly the historical ``_eq2_passed`` arithmetic, returning
        the (nq,) threshold instead of the pass mask (``passed = crude
        < thr[:, None]`` — the same expression, evaluated by the
        refine stage)."""
        from repro.index.base import lut_sum
        neg_c, cand = jax.lax.top_k(-crude, self.topk)       # (nq,topk)
        cand_codes = jnp.take(codes, cand, axis=0)           # (nq,topk,K)
        if self.code_bits == 4:
            cand_codes = widen_codes(cand_codes, luts.shape[1],
                                     self.code_bits)
        if not self.quantized:
            full_cand = lut_sum(luts, cand_codes)            # (nq,topk)
        else:
            full_cand = -neg_c + lut_sum(luts, cand_codes, ~fast)
        far = jnp.argmax(full_cand, axis=1)                  # (nq,)
        t = -jnp.take_along_axis(neg_c, far[:, None], axis=1)[:, 0]
        return t + sigma

    def from_candidates(self, luts, codes, cand_vals, cand_idx, fast,
                        sigma):
        """Bootstrap from the fused crude kernel's running top-k (flat
        pallas path): candidate full distances are crude + exact-slow
        on either LUT dtype (the kernel already dequantized
        ``cand_vals`` to true-distance f32)."""
        from repro.index.base import lut_sum
        cand_codes = jnp.take(codes, cand_idx, axis=0)       # (nq,topk,K)
        if self.code_bits == 4:
            cand_codes = widen_codes(cand_codes, luts.shape[1],
                                     self.code_bits)
        full_cand = cand_vals + lut_sum(luts, cand_codes, ~fast)
        far = jnp.argmax(full_cand, axis=1)
        t = jnp.take_along_axis(cand_vals, far[:, None], axis=1)[:, 0]
        return t + sigma

    def from_dense_slab(self, luts, cand_codes, crude, fast, sigma):
        """IVF bootstrap from the dense slab crude (jnp path): the slab
        may hold fewer than topk valid candidates — invalid entries
        rank +inf and are excluded from the far-element argmax."""
        from repro.index.base import lut_sum
        neg_c, cand = jax.lax.top_k(-crude, self.topk)       # (nq, topk)
        cand_top = jnp.take_along_axis(
            cand_codes, cand[:, :, None], axis=1)            # (nq,topk,K)
        cand_top = widen_codes(cand_top, luts.shape[1], self.code_bits)
        if not self.quantized:
            full_cand = lut_sum(luts, cand_top)
        else:
            full_cand = -neg_c + lut_sum(luts, cand_top, ~fast)
        far = jnp.argmax(
            jnp.where(jnp.isfinite(-neg_c), full_cand, -jnp.inf), axis=1)
        t = -jnp.take_along_axis(neg_c, far[:, None], axis=1)[:, 0]
        return t + sigma

    def from_slab_candidates(self, luts, cand_codes, cand_vals, cand_pos,
                             fast, sigma):
        """IVF bootstrap from the fused slab kernel's running top-k
        (pallas path); +inf slots (slabs thinner than topk) are
        excluded from the far-element argmax."""
        from repro.index.base import lut_sum
        ok = jnp.isfinite(cand_vals)
        pos_safe = jnp.where(ok, cand_pos, 0)
        cand_top = jnp.take_along_axis(cand_codes, pos_safe[:, :, None],
                                       axis=1)
        cand_top = widen_codes(cand_top, luts.shape[1], self.code_bits)
        full_cand = cand_vals + lut_sum(luts, cand_top, ~fast)
        far = jnp.argmax(jnp.where(ok, full_cand, -jnp.inf), axis=1)
        t = jnp.take_along_axis(cand_vals, far[:, None], axis=1)[:, 0]
        return t + sigma


@dataclasses.dataclass(frozen=True)
class RefineStage:
    """Phase 2 of eq. 2: slow-codebook sums for margin-test survivors
    and the final full-distance top-k (eq. 1: full = crude + slow).
    The last reader of the dense crude matrix — the pipelined executor
    donates the crude carry into this stage."""
    backend: str = "jnp"
    topk: int = 50
    block_q: int = 64
    block_n: int = 512
    interpret: Optional[bool] = None
    code_bits: int = 8

    buffers = BufferSpec(
        borrows=("codes | cand_codes", "luts (slow tiles)", "thr",
                 "safe ids", "filter pred"),
        owns=("dist", "idx"),
        donates=("crude",))

    def __call__(self, codes, luts, crude, thr, fast, *, pred=None):
        """Flat refine.  Returns (idx, dist, passed) — ``passed`` is
        the (nq, n) margin-test mask (the pass-rate accounting input);
        the pallas path reports it as the equivalent mask recomputed
        from the crude carry (identical: the kernel evaluates the same
        expression in-kernel)."""
        from repro.index.base import (lut_sum, mask_filtered_ids,
                                      nibble_lut_sum)
        if self.backend == "pallas":
            from repro.kernels import ops
            lut_slow = slow_lut_operand(luts, fast,
                                        code_bits=self.code_bits)
            dist, idx = ops.batched_refine_topk(
                codes, lut_slow, crude, thr, self.topk,
                block_q=self.block_q, block_n=self.block_n,
                interpret=self.interpret, code_bits=self.code_bits)
            return idx, dist, crude < thr[:, None]
        K = luts.shape[1]
        slow = (nibble_lut_sum(luts, codes, K, ~fast)
                if self.code_bits == 4 else lut_sum(luts, codes, ~fast))
        passed = crude < thr[:, None]
        ranked = jnp.where(passed, crude + slow, jnp.inf)
        neg, idx = jax.lax.top_k(-ranked, self.topk)
        if pred is not None:
            idx = mask_filtered_ids(idx, -neg)
        return idx, -neg, passed

    def slab(self, cand_codes, luts, crude, thr, fast, safe, *,
             slow=None, pred=None):
        """IVF refine over the candidate slab.  ``safe`` maps slab
        positions back to global db ids; the jnp path consumes the
        ``slow`` accumulator the crude stage fused into its sweep."""
        from repro.index.base import mask_filtered_ids
        if self.backend == "pallas":
            from repro.kernels import ops
            lut_slow = slow_lut_operand(luts, fast,
                                        code_bits=self.code_bits)
            dist, pos = ops.ivf_refine_topk(
                cand_codes, lut_slow, crude, thr, self.topk,
                block_q=self.block_q, block_n=self.block_n,
                interpret=self.interpret, code_bits=self.code_bits)
            # merged positions are always real slab columns (the slab
            # is padded to >= topk); clip only guards take_along_axis
            ids = jnp.take_along_axis(
                safe, jnp.minimum(pos, safe.shape[1] - 1), axis=1)
            return ids, dist, crude < thr[:, None]
        passed = crude < thr[:, None]            # invalid -> inf -> False
        ranked = jnp.where(passed, crude + slow, jnp.inf)
        neg, pos = jax.lax.top_k(-ranked, self.topk)
        ids = jnp.take_along_axis(safe, pos, axis=1)
        if pred is not None:
            ids = mask_filtered_ids(ids, -neg)
        return ids, -neg, passed


def two_step_stages(*, backend: str, topk: int, block_q: int, block_n: int,
                    interpret=None, quantized: bool = False,
                    code_bits: int = 8, want_crude: bool = True):
    """The standard crude→threshold→refine triple for one engine
    configuration — the composition every two-step search path (flat
    and IVF, monolithic and pipelined) is built from."""
    crude = CrudeStage(backend=backend, topk=topk, block_q=block_q,
                       block_n=block_n, interpret=interpret,
                       quantized=quantized, code_bits=code_bits,
                       want_crude=want_crude)
    thr = ThresholdStage(topk=topk, quantized=quantized,
                         code_bits=code_bits)
    refine = RefineStage(backend=backend, topk=topk, block_q=block_q,
                         block_n=block_n, interpret=interpret,
                         code_bits=code_bits)
    return crude, thr, refine
