"""Pallas TPU kernels: batched fused two-step search (DESIGN.md §3.5)
and the IVF candidate-slab variants (DESIGN.md §7).

The serving-shaped hot path: a (query-tile x point-tile) grid where a
tile of per-query flattened LUTs (blk_q, |books|*m) is pinned in VMEM
for the whole inner sweep over point tiles, and each codes tile
(blk_n, K) streamed HBM->VMEM is reused by *all* blk_q queries in the
tile — vs the per-query formulation that re-streams the entire codes
array once per query.  Distances come from a one-hot(codes) x LUT^T
matmul on the MXU: (blk_n, |books|*m) @ (|books|*m, blk_q) -> a
(blk_q, blk_n) distance tile per grid step.

Each kernel contracts only over the codebooks its pass sums: a static
tuple ``books`` of codebook ids (default all K), and a LUT operand
narrowed outside the kernel to those codebooks' columns, in that
order.  The one-hot spans only the listed codebooks — the crude pass
the fast set (|K_fast|*m columns), refine the slow set
((K - |K_fast|)*m) — so no MXU pass or one-hot compare is spent on a
codebook whose table the pass would zero.  The fast set is
interleaved: its ids need not be contiguous.  The codes tile is
streamed whole; the listed columns are picked in VMEM.  The search
stages take ``books`` from the index's concrete fast mask
(``stages.pass_books``); a mask that arrives traced keeps the masked
full-width operand with every codebook listed.

Two kernels:

  crude_topk   phase 1 — crude LUT sums over the fast codebooks for
               every point, plus an in-kernel running top-k of the
               crude distances (the eq. 2 threshold bootstrap
               candidates), merged across point tiles in VMEM.
  refine_topk  phase 2 — fused eq. 2 threshold test (crude < t + sigma),
               slow-codebook LUT sum for survivors, and an in-kernel
               top-k merge of the full distances.  Pruned points never
               enter the ranking.

The running top-k merge (``stages.merge_topk``) keeps the ``topk``
smallest (distance, global index) keys seen so far with compares and
min/max reductions (Mosaic has no sort), and ``stages.finish_topk``
orders them after the last point tile.  The two-key order reproduces
``jax.lax.top_k``'s lowest-index-wins tie-breaking *globally* —
returned indices are bit-identical to a monolithic top-k over the full
distance row, including the all-ties +inf tail when fewer than ``topk``
points survive the margin test.

Precision: the shared-codes kernels take an f32 table as an exact
three-part bfloat16 split, made once a call in the wrapper
(``split_lut``): hi = bf16(x), mid = bf16(x - hi), lo = bf16(x - hi -
mid), each rounded to nearest and kept as f32 values.  Each part is
exactly a bfloat16 and (hi + mid) + lo == x bit for bit: round to
nearest leaves at most 16 bits after hi and 8 after mid.  Only an |x|
below 2^-103 (about 1e-31) could lose bits: its lowest bits lie below
f32's normal range, and the rounding flushes them to zero.  The parts
of a query tile are stacked on its row axis, so the kernel issues one
default-precision dot of (3 * blk_q, |books|*m) against the 0/1
one-hot, which is exact in bfloat16 too, and sums the three row blocks
as (S_hi + S_mid) + S_lo: the selected entries' sum to within a few
f32 ulps, as ``Precision.HIGHEST`` gives it.  On a v5e the MXU takes
only bf16 operands, and Mosaic lowers an f32 dot at HIGHEST to a
six-pass emulation that reloads the one-hot's weights for every pass;
the split takes one pass a part, and f32 operands holding bf16 values
need no VPU pack.  The IVF slab kernels keep HIGHEST: Mosaic lowers
their batched mat-vec to VPU multiply-adds, exact in f32 already.

Both kernels accept arbitrary (non-divisible) n and nq: inputs are
zero-padded up to the tile grid and pad columns are masked to +inf
before the merge (the dense crude matrix is simply sliced).

Codes enter in their *stored* packed dtype (uint8 for m <= 256) and are
widened to int32 per-tile inside the kernel — the HBM->VMEM stream
carries 1 byte/entry, which is the 4x traffic saving the packing is for.

Quantized-LUT mode (DESIGN.md §8): the crude kernels also accept
*int8* LUT tiles (``lut_flat`` dtype int8, plus per-query ``lut_scale``
/ ``lut_offset`` f32 columns).  The one-hot dot then runs int8 x int8
with ``preferred_element_type=int32`` — the MXU's native quantized
form — and the (blk_q, blk_n) int32 tile is rescaled in-VMEM to
true-distance f32 (``scale * acc + offset``) before the masking/top-k
merge, which is therefore unchanged.  An int8 tile is 4x smaller than
f32, doubling-and-more the LUT capacity that can stay VMEM-pinned per
query tile.  The refine kernels are f32-only on purpose: eq. 2's exact
re-ranking (the slow/full pass) must not be quantized.

Fast-scan mode (``code_bits=4``, DESIGN.md §12): with 16-codeword
codebooks two codes pack into one byte, so the codes stream halves
again — every kernel accepts ``code_bits=4`` with nibble-packed codes
((n, ceil(K/2)) uint8) and unpacks them in-VMEM via shift/mask before
the one-hot dot.  The codebook list applies after the unpack; a
full-width LUT operand covers the *even-padded* K (odd K gets an
all-zero sentinel codebook — ``index.base.pad_luts_even`` /
``fastscan_kernel_operands``), so sentinel nibbles contribute exactly
zero, and a narrowed one never lists the sentinel.  The dequant affine
(offset counts real codebooks only) is unchanged from the 8-bit int8
path; the 16-entry int8 LUT columns accumulate through the same
``preferred_element_type=int32`` dot with one rescale at tile end.  ``fastscan_crude_topk_pallas`` /
``ivf_fastscan_crude_topk_pallas`` are the named crude entry points.

IVF variants (``ivf_crude_topk_pallas`` / ``ivf_refine_topk_pallas``):
same two-phase structure, but the codes operand is the *gathered
candidate slab* (nq, nc, K) — per-query candidates, so the distance
tile is a batched matvec ``(blk_q, blk_n, |books|*m) x (blk_q,
|books|*m)`` instead of the shared-codes matmul.  Candidate validity
rides in as the global id slab (pad id -1): invalid and grid-pad
columns are masked to +inf *in the dense crude output* so phase 2
needs no separate mask.  Top-k indices are slab positions (probe-slot
major), mapped back to global db ids by the caller.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.adc import flat_onehot
# The tile helpers shared by every fused kernel live in the stage
# module (DESIGN.md §13) — one definition serves batched_search,
# icm_encode, ops, and the stage objects.
from repro.kernels.stages import (check_quantized_args as
                                  _check_quantized_args,
                                  finish_topk as _finish_topk,
                                  init_topk as _init_topk,
                                  merge_topk as _merge_topk,
                                  pad_to as _pad_to,
                                  resolve_kernel_code_bits as
                                  _resolve_kernel_code_bits,
                                  unpack_nibble_tile as
                                  _unpack_nibble_tile)

# the slab kernels' f32 one-hot dot: exact selection of each entry
# (module docstring); the shared-codes kernels split the table instead
_F32_DOT = jax.lax.Precision.HIGHEST
_ROWS = (((1,), (1,)), ((), ()))        # (rows, Km) x (blk_n, Km)^T


def _dot_kwargs(lut):
    """int8 tables dot int8 x int8 into int32 (the MXU's quantized
    form); f32 tables, which only the slab kernels dot whole, at
    HIGHEST, which selects each entry exactly."""
    if lut.dtype == jnp.int8:
        return dict(preferred_element_type=jnp.int32)
    return dict(precision=_F32_DOT, preferred_element_type=jnp.float32)


def bf16_parts(x):
    """f32 x -> (hi, mid, lo) f32 arrays, each exactly a bfloat16 value,
    with (hi + mid) + lo == x bit for bit (module docstring)."""
    def bf16(v):
        return jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)

    hi = bf16(x)
    mid = bf16(x - hi)
    return hi, mid, bf16(x - hi - mid)


def split_lut(lut, block_q: int):
    """(nq_pad, Km) f32 tables -> (3 * nq_pad, Km): each block_q-row
    query tile becomes its hi, mid and lo rows stacked, the block the
    shared-codes kernels pin as (3 * block_q, Km)."""
    nq_pad, km = lut.shape
    parts = jnp.stack([p.reshape(-1, block_q, km) for p in bf16_parts(lut)],
                      axis=1)
    return parts.reshape(3 * nq_pad, km)


def _tile_distances(codes, lut, K: int, m: int, books):
    """Shared-codes tile distances: codes (blk_n, K) int32 against the
    LUT tile of the listed codebooks -> (blk_q, blk_n) f32 | int32, one
    narrowed one-hot and one dot.  An int8 tile is (blk_q, |books|*m);
    an f32 tile is ``split_lut``'s (3 * blk_q, |books|*m), dotted at
    default precision and its row blocks summed (S_hi + S_mid) + S_lo.
    (One m-wide dot per listed codebook ran as fast here and slower in
    the slab kernels on a v5e.)"""
    onehot = flat_onehot(codes, K, m, lut.dtype, books)
    if lut.dtype == jnp.int8:
        return jax.lax.dot_general(lut, onehot, _ROWS, **_dot_kwargs(lut))
    parts = jax.lax.dot_general(lut, onehot, _ROWS,
                                preferred_element_type=jnp.float32)
    blk_q = parts.shape[0] // 3
    return (parts[:blk_q] + parts[blk_q:2 * blk_q]) + parts[2 * blk_q:]


def _merge_tile(vals_ref, idx_ref, ranked, gidx, topk: int):
    """The per-grid-step top-k epilogue every fused kernel shares: seed
    the carry on the first point tile, merge this tile, and order the
    carry after the last one."""
    ni = pl.program_id(1)

    @pl.when(ni == 0)
    def _():
        _init_topk(vals_ref, idx_ref)

    _merge_topk(vals_ref, idx_ref, ranked, gidx, topk)

    @pl.when(ni == pl.num_programs(1) - 1)
    def _():
        _finish_topk(vals_ref, idx_ref)


def _crude_topk_kernel(codes_ref, lut_ref, *refs,
                       K: int, m: int, books, topk: int, n: int, blk_n: int,
                       want_crude: bool, quantized: bool,
                       nibble: bool = False):
    ni = pl.program_id(1)
    codes = codes_ref[...].astype(jnp.int32)     # widen packed codes per-tile
    if nibble:
        codes = _unpack_nibble_tile(codes)       # (blk_n, K) fast-scan mode
    lut = lut_ref[...]     # f32 (3 * blk_q, |books|*m) parts | int8 (blk_q, .)
    acc = _tile_distances(codes, lut, K, m, books)    # (blk_q, blk_n) MXU
    blk_q = acc.shape[0]
    if quantized:
        scale_ref, offset_ref, *refs = refs
        # rescale to true-distance f32: codebooks outside the fast set
        # are absent or zero in the int8 tile, so only the offset
        # (= |K_fast| * bias) corrects them
        crude = scale_ref[...] * acc.astype(jnp.float32) + offset_ref[...]
    else:
        crude = acc
    if want_crude:
        crude_ref, vals_ref, idx_ref = refs
        crude_ref[...] = crude
    else:
        vals_ref, idx_ref = refs

    gidx = ni * blk_n + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_n), 1)
    masked = jnp.where(gidx < n, crude, jnp.inf)      # hide pad columns

    _merge_tile(vals_ref, idx_ref, masked, gidx, topk)


def _refine_topk_kernel(codes_ref, lut_ref, crude_ref, thr_ref,
                        vals_ref, idx_ref,
                        *, K: int, m: int, books, topk: int, n: int,
                        blk_n: int, nibble: bool = False):
    ni = pl.program_id(1)
    codes = codes_ref[...].astype(jnp.int32)     # widen packed codes per-tile
    if nibble:
        codes = _unpack_nibble_tile(codes)
    lut = lut_ref[...]                # (3 * blk_q, |books|*m) f32 parts
    crude = crude_ref[...]                       # (blk_q, blk_n) f32
    thr = thr_ref[...]                           # (blk_q, 1) f32 = t + sigma
    blk_q = crude.shape[0]
    slow = _tile_distances(codes, lut, K, m, books)
    full = crude + slow                               # eq. 1 refinement

    gidx = ni * blk_n + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_n), 1)
    passed = (crude < thr) & (gidx < n)               # eq. 2 margin test
    ranked = jnp.where(passed, full, jnp.inf)

    _merge_tile(vals_ref, idx_ref, ranked, gidx, topk)


@functools.partial(jax.jit,
                   static_argnames=("topk", "block_q", "block_n", "interpret",
                                    "want_crude", "code_bits", "books"))
def crude_topk_pallas(codes, lut_flat, lut_scale=None, lut_offset=None, *,
                      topk: int, block_q: int = 64, block_n: int = 512,
                      interpret: bool = True, want_crude: bool = True,
                      code_bits: int = 8, books=None):
    """Phase 1.  codes (n, K) int (packed dtypes welcome — widened
    per-tile in-kernel), lut_flat (nq, len(books)*m) flattened tables
    of the summed codebooks ``books`` (static ids in column order,
    default all K), f32 *or* int8 (quantized-LUT mode, DESIGN.md §8: int8
    requires ``lut_scale`` (nq,) and ``lut_offset`` (nq,) f32 — the
    per-query dequant affine, offset already multiplied by the summed
    codebook count) -> (crude (nq, n) f32, cand_vals (nq, topk) f32,
    cand_idx (nq, topk) i32).  Crude values are always returned in
    true-distance f32 units, whatever the LUT dtype.

    ``code_bits=4`` is fast-scan mode (DESIGN.md §12): codes arrive
    nibble-packed (n, ceil(K/2)) uint8 and are unpacked in-VMEM via
    shift/mask; a full-width ``lut_flat`` must cover the even-padded K
    (an all-zero sentinel codebook for odd K —
    ``index.base.pad_luts_even`` / ``fastscan_kernel_operands``), so the
    dot and dequant are otherwise identical to the 8-bit path and
    rankings match it bitwise.

    ``want_crude=False`` skips writing the dense (nq, n) crude matrix
    to HBM (one-step ADC only needs the top-k) and returns crude=None.

    Padding: n and nq are padded up to the (block_q, block_n) grid
    (``_pad_to``); pad point columns are masked to +inf before the
    in-kernel merge and all outputs are sliced back to (nq, ...)."""
    quantized = _check_quantized_args(lut_flat, lut_scale, lut_offset)
    n, Kc = codes.shape
    nq, Km = lut_flat.shape
    K, m, books = _resolve_kernel_code_bits(code_bits, Kc, Km, books)
    n_pad = pl.cdiv(n, block_n) * block_n
    nq_pad = pl.cdiv(nq, block_q) * block_q
    grid = (nq_pad // block_q, n_pad // block_n)
    topk_shapes = (jax.ShapeDtypeStruct((nq_pad, topk), jnp.float32),
                   jax.ShapeDtypeStruct((nq_pad, topk), jnp.int32))
    topk_specs = (pl.BlockSpec((block_q, topk), lambda qi, ni: (qi, 0)),
                  pl.BlockSpec((block_q, topk), lambda qi, ni: (qi, 0)))
    crude_shape = (jax.ShapeDtypeStruct((nq_pad, n_pad), jnp.float32),)
    crude_spec = (pl.BlockSpec((block_q, block_n), lambda qi, ni: (qi, ni)),)
    lut_rows = block_q if quantized else 3 * block_q
    in_specs = [
        pl.BlockSpec((block_n, Kc), lambda qi, ni: (ni, 0)),
        pl.BlockSpec((lut_rows, Km), lambda qi, ni: (qi, 0)),  # pinned
    ]
    lut_p = _pad_to(lut_flat, nq_pad)
    if not quantized:
        lut_p = split_lut(lut_p.astype(jnp.float32), block_q)
    operands = [_pad_to(codes, n_pad), lut_p]
    if quantized:
        col = pl.BlockSpec((block_q, 1), lambda qi, ni: (qi, 0))
        in_specs += [col, col]
        operands += [
            _pad_to(jnp.asarray(lut_scale, jnp.float32)[:, None], nq_pad),
            _pad_to(jnp.asarray(lut_offset, jnp.float32)[:, None], nq_pad)]
    outs = pl.pallas_call(
        functools.partial(_crude_topk_kernel, K=K, m=m, books=books,
                          topk=topk, n=n,
                          blk_n=block_n, want_crude=want_crude,
                          quantized=quantized, nibble=code_bits == 4),
        out_shape=(crude_shape if want_crude else ()) + topk_shapes,
        grid=grid,
        in_specs=in_specs,
        out_specs=(crude_spec if want_crude else ()) + topk_specs,
        interpret=interpret,
    )(*operands)
    if want_crude:
        crude, vals, idx = outs
        return crude[:nq, :n], vals[:nq], idx[:nq]
    vals, idx = outs
    return None, vals[:nq], idx[:nq]


# ------------------------------------------------------- IVF slab kernels ----

def _slab_distances(codes, lut, K: int, m: int, books):
    """Per-query candidate-slab distances: codes (blk_q, blk_n, K) int32,
    lut (blk_q, len(books)*m) f32 | int8 tables of the listed codebooks
    -> (blk_q, blk_n) f32 | int32 via a batched onehot-matvec (one
    MXU-shaped dot per query row; int8 LUTs dot int8 x int8 into an
    int32 tile — the caller rescales).

    VMEM sizing: the one-hot intermediate is blk_q * blk_n *
    len(books)*m at the LUT's width — unlike the shared-codes kernels
    there is one one-hot *per query row*.  Tile sizes must keep that
    times 4 B well under VMEM (the 8 x 128 defaults give at most 8 MB at
    K=8, m=256, f32; int8 one-hots are 4x smaller); raising blk_q is the
    expensive axis."""
    blk_q, blk_n, _ = codes.shape
    onehot = flat_onehot(codes.reshape(blk_q * blk_n, K), K, m, lut.dtype,
                         books).reshape(blk_q, blk_n, -1)
    return jax.lax.dot_general(onehot, lut, (((2,), (1,)), ((0,), (0,))),
                               **_dot_kwargs(lut))


def _ivf_crude_kernel(codes_ref, ids_ref, lut_ref, *refs,
                      K: int, m: int, books, topk: int, nc: int, blk_n: int,
                      quantized: bool, nibble: bool = False):
    ni = pl.program_id(1)
    codes = codes_ref[...].astype(jnp.int32)     # (blk_q, blk_n, K)
    if nibble:
        codes = _unpack_nibble_tile(codes)
    ids = ids_ref[...]                           # (blk_q, blk_n) global ids
    lut = lut_ref[...]                  # (blk_q, |books|*m) f32 | int8
    if quantized:
        scale_ref, offset_ref, crude_ref, vals_ref, idx_ref = refs
        acc = _slab_distances(codes, lut, K, m, books)   # int32
        crude = (scale_ref[...] * acc.astype(jnp.float32)
                 + offset_ref[...])
    else:
        crude_ref, vals_ref, idx_ref = refs
        crude = _slab_distances(codes, lut, K, m, books)

    blk_q = lut.shape[0]
    gidx = ni * blk_n + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_n), 1)
    # invalid (-1 pad) and grid-pad columns become +inf in the *dense*
    # output, so the refine phase inherits the mask through crude
    masked = jnp.where((ids >= 0) & (gidx < nc), crude, jnp.inf)
    crude_ref[...] = masked

    _merge_tile(vals_ref, idx_ref, masked, gidx, topk)


def _ivf_refine_kernel(codes_ref, lut_ref, crude_ref, thr_ref, vals_ref,
                       idx_ref, *, K: int, m: int, books, topk: int, nc: int,
                       blk_n: int, nibble: bool = False):
    ni = pl.program_id(1)
    codes = codes_ref[...].astype(jnp.int32)
    if nibble:
        codes = _unpack_nibble_tile(codes)
    lut = lut_ref[...]                           # (blk_q, |books|*m) slow
    crude = crude_ref[...]                       # (blk_q, blk_n) inf-masked
    thr = thr_ref[...]                           # (blk_q, 1)
    slow = _slab_distances(codes, lut, K, m, books)
    full = crude + slow                          # eq. 1 refinement

    blk_q = lut.shape[0]
    gidx = ni * blk_n + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_n), 1)
    passed = crude < thr                         # invalid columns are +inf
    ranked = jnp.where(passed & (gidx < nc), full, jnp.inf)

    _merge_tile(vals_ref, idx_ref, ranked, gidx, topk)


@functools.partial(jax.jit,
                   static_argnames=("topk", "block_q", "block_n", "interpret",
                                    "code_bits", "books"))
def ivf_crude_topk_pallas(cand_codes, cand_ids, lut_flat, lut_scale=None,
                          lut_offset=None, *, topk: int, block_q: int = 8,
                          block_n: int = 128, interpret: bool = True,
                          code_bits: int = 8, books=None):
    """IVF phase 1 over the gathered candidate slab.

    cand_codes (nq, nc, K) int (packed dtypes welcome — widened
    per-tile in-kernel), cand_ids (nq, nc) int32 global db ids (-1
    pad), lut_flat (nq, len(books)*m) tables of the summed codebooks
    ``books`` (default all K), f32 *or* int8
    (quantized-LUT mode: int8 requires ``lut_scale`` / ``lut_offset``
    (nq,) f32, see ``crude_topk_pallas``) -> (crude (nq, nc) f32 with
    invalid columns +inf, cand_vals (nq, topk) f32, cand_pos (nq, topk)
    i32 slab positions).  Crude values are always true-distance f32.

    ``code_bits=4`` is the fast-scan slab variant: cand_codes arrive
    nibble-packed (nq, nc, ceil(K/2)) uint8, unpacked in-VMEM via
    shift/mask against an even-K-padded ``lut_flat`` (see
    ``crude_topk_pallas``).

    Padding: nq and nc are padded up to the (block_q, block_n) grid
    (``_pad_to`` on the query axis; the slab pad columns carry id -1 so
    they mask to +inf like in-slab invalid candidates); outputs are
    sliced back to (nq, nc)/(nq, topk)."""
    quantized = _check_quantized_args(lut_flat, lut_scale, lut_offset)
    nq, nc, Kc = cand_codes.shape
    Km = lut_flat.shape[1]
    K, m, books = _resolve_kernel_code_bits(code_bits, Kc, Km, books)
    nc_pad = pl.cdiv(nc, block_n) * block_n
    nq_pad = pl.cdiv(nq, block_q) * block_q
    grid = (nq_pad // block_q, nc_pad // block_n)
    codes_p = jnp.pad(cand_codes, ((0, nq_pad - nq), (0, nc_pad - nc),
                                   (0, 0)))
    ids_p = jnp.pad(cand_ids, ((0, nq_pad - nq), (0, nc_pad - nc)),
                    constant_values=-1)
    in_specs = [
        pl.BlockSpec((block_q, block_n, Kc), lambda qi, ni: (qi, ni, 0)),
        pl.BlockSpec((block_q, block_n), lambda qi, ni: (qi, ni)),
        pl.BlockSpec((block_q, Km), lambda qi, ni: (qi, 0)),   # pinned
    ]
    operands = [codes_p, ids_p,
                _pad_to(lut_flat if quantized
                        else lut_flat.astype(jnp.float32), nq_pad)]
    if quantized:
        col = pl.BlockSpec((block_q, 1), lambda qi, ni: (qi, 0))
        in_specs += [col, col]
        operands += [
            _pad_to(jnp.asarray(lut_scale, jnp.float32)[:, None], nq_pad),
            _pad_to(jnp.asarray(lut_offset, jnp.float32)[:, None], nq_pad)]
    crude, vals, idx = pl.pallas_call(
        functools.partial(_ivf_crude_kernel, K=K, m=m, books=books,
                          topk=topk, nc=nc,
                          blk_n=block_n, quantized=quantized,
                          nibble=code_bits == 4),
        out_shape=(jax.ShapeDtypeStruct((nq_pad, nc_pad), jnp.float32),
                   jax.ShapeDtypeStruct((nq_pad, topk), jnp.float32),
                   jax.ShapeDtypeStruct((nq_pad, topk), jnp.int32)),
        grid=grid,
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((block_q, block_n), lambda qi, ni: (qi, ni)),
            pl.BlockSpec((block_q, topk), lambda qi, ni: (qi, 0)),
            pl.BlockSpec((block_q, topk), lambda qi, ni: (qi, 0)),
        ),
        interpret=interpret,
    )(*operands)
    return crude[:nq, :nc], vals[:nq], idx[:nq]


@functools.partial(jax.jit,
                   static_argnames=("topk", "block_q", "block_n", "interpret",
                                    "code_bits", "books"))
def ivf_refine_topk_pallas(cand_codes, lut_flat, crude, thresholds, *,
                           topk: int, block_q: int = 8, block_n: int = 128,
                           interpret: bool = True, code_bits: int = 8,
                           books=None):
    """IVF phase 2 over the candidate slab.  cand_codes (nq, nc, K) int
    (packed dtypes welcome; nibble-packed (nq, nc, ceil(K/2)) under
    ``code_bits=4``), lut_flat (nq, len(books)*m) f32 slow tables of
    the codebooks ``books`` (default all K — always f32: the refine
    pass is eq. 2's exact re-ranking and is never quantized), crude
    (nq, nc) f32 from phase 1
    (invalid columns +inf; a quantized phase 1 already emits dequantized
    f32), thresholds (nq,) f32 = t + sigma -> (dist (nq, topk) f32, pos
    (nq, topk) i32 slab positions).

    Padding: nq/nc padded up to the grid; the crude matrix is embedded
    in a +inf canvas so pad columns can never pass the margin test, and
    outputs are sliced back to (nq, topk)."""
    nq, nc, Kc = cand_codes.shape
    Km = lut_flat.shape[1]
    K, m, books = _resolve_kernel_code_bits(code_bits, Kc, Km, books)
    nc_pad = pl.cdiv(nc, block_n) * block_n
    nq_pad = pl.cdiv(nq, block_q) * block_q
    grid = (nq_pad // block_q, nc_pad // block_n)
    codes_p = jnp.pad(cand_codes, ((0, nq_pad - nq), (0, nc_pad - nc),
                                   (0, 0)))
    crude_p = jnp.full((nq_pad, nc_pad), jnp.inf, jnp.float32)
    crude_p = jax.lax.dynamic_update_slice(
        crude_p, crude.astype(jnp.float32), (0, 0))
    thr = _pad_to(jnp.asarray(thresholds, jnp.float32)[:, None], nq_pad)
    vals, idx = pl.pallas_call(
        functools.partial(_ivf_refine_kernel, K=K, m=m, books=books,
                          topk=topk, nc=nc,
                          blk_n=block_n, nibble=code_bits == 4),
        out_shape=(jax.ShapeDtypeStruct((nq_pad, topk), jnp.float32),
                   jax.ShapeDtypeStruct((nq_pad, topk), jnp.int32)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, block_n, Kc), lambda qi, ni: (qi, ni, 0)),
            pl.BlockSpec((block_q, Km), lambda qi, ni: (qi, 0)),   # pinned
            pl.BlockSpec((block_q, block_n), lambda qi, ni: (qi, ni)),
            pl.BlockSpec((block_q, 1), lambda qi, ni: (qi, 0)),
        ],
        out_specs=(
            pl.BlockSpec((block_q, topk), lambda qi, ni: (qi, 0)),
            pl.BlockSpec((block_q, topk), lambda qi, ni: (qi, 0)),
        ),
        interpret=interpret,
    )(codes_p, _pad_to(lut_flat.astype(jnp.float32), nq_pad), crude_p, thr)
    return vals[:nq], idx[:nq]


@functools.partial(jax.jit,
                   static_argnames=("topk", "block_q", "block_n", "interpret",
                                    "code_bits", "books"))
def refine_topk_pallas(codes, lut_flat, crude, thresholds, *, topk: int,
                       block_q: int = 64, block_n: int = 512,
                       interpret: bool = True, code_bits: int = 8,
                       books=None):
    """Phase 2.  codes (n, K) int (packed dtypes welcome — widened
    per-tile in-kernel; nibble-packed (n, ceil(K/2)) under
    ``code_bits=4``), lut_flat (nq, len(books)*m) f32 slow tables of
    the codebooks ``books`` (default all K — always f32: the refine
    pass is eq. 2's exact re-ranking and is never quantized), crude
    (nq, n) f32 from phase 1
    (a quantized phase 1 already emits dequantized f32), thresholds
    (nq,) f32 = t + sigma -> (dist (nq, topk) f32, idx (nq, topk) i32);
    pruned points rank +inf.

    Padding: n/nq padded up to the grid (``_pad_to``); the crude matrix
    is embedded in a +inf canvas so pad columns can never pass the
    margin test, and outputs are sliced back to (nq, topk)."""
    n, Kc = codes.shape
    nq, Km = lut_flat.shape
    K, m, books = _resolve_kernel_code_bits(code_bits, Kc, Km, books)
    n_pad = pl.cdiv(n, block_n) * block_n
    nq_pad = pl.cdiv(nq, block_q) * block_q
    grid = (nq_pad // block_q, n_pad // block_n)
    # pad crude with +inf so pad columns can never pass the margin test
    crude_p = jnp.full((nq_pad, n_pad), jnp.inf, jnp.float32)
    crude_p = jax.lax.dynamic_update_slice(
        crude_p, crude.astype(jnp.float32), (0, 0))
    thr = _pad_to(jnp.asarray(thresholds, jnp.float32)[:, None], nq_pad)
    vals, idx = pl.pallas_call(
        functools.partial(_refine_topk_kernel, K=K, m=m, books=books,
                          topk=topk, n=n,
                          blk_n=block_n, nibble=code_bits == 4),
        out_shape=(jax.ShapeDtypeStruct((nq_pad, topk), jnp.float32),
                   jax.ShapeDtypeStruct((nq_pad, topk), jnp.int32)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, Kc), lambda qi, ni: (ni, 0)),
            pl.BlockSpec((3 * block_q, Km),
                         lambda qi, ni: (qi, 0)),              # pinned
            pl.BlockSpec((block_q, block_n), lambda qi, ni: (qi, ni)),
            pl.BlockSpec((block_q, 1), lambda qi, ni: (qi, 0)),
        ],
        out_specs=(
            pl.BlockSpec((block_q, topk), lambda qi, ni: (qi, 0)),
            pl.BlockSpec((block_q, topk), lambda qi, ni: (qi, 0)),
        ),
        interpret=interpret,
    )(_pad_to(codes, n_pad),
      split_lut(_pad_to(lut_flat.astype(jnp.float32), nq_pad), block_q),
      crude_p, thr)
    return vals[:nq], idx[:nq]


def fastscan_crude_topk_pallas(packed_codes, lut_flat, lut_scale=None,
                               lut_offset=None, **opts):
    """The 4-bit fast-scan crude kernel (DESIGN.md §12):
    ``crude_topk_pallas`` over nibble-packed codes ((n, ceil(K/2))
    uint8, in-VMEM shift/mask unpack).  ``lut_flat`` must be the
    even-K-padded operand from ``index.base.fastscan_kernel_operands``
    (int8) or ``pad_luts_even`` (f32)."""
    return crude_topk_pallas(packed_codes, lut_flat, lut_scale,
                             lut_offset, code_bits=4, **opts)


def ivf_fastscan_crude_topk_pallas(packed_cand_codes, cand_ids, lut_flat,
                                   lut_scale=None, lut_offset=None, **opts):
    """The 4-bit fast-scan IVF slab crude kernel:
    ``ivf_crude_topk_pallas`` over a nibble-packed candidate slab
    ((nq, nc, ceil(K/2)) uint8); see ``fastscan_crude_topk_pallas``."""
    return ivf_crude_topk_pallas(packed_cand_codes, cand_ids, lut_flat,
                                 lut_scale, lut_offset, code_bits=4, **opts)
