"""IVF (inverted-file) coarse partitioning composed with ICQ — the
paper's path to sub-linear query cost, batched for serving traffic
(DESIGN.md §7).

A coarse k-means splits the database into ``n_lists`` cells; a query
visits only the ``n_probe`` nearest cells and runs the ICQ two-step
search over those candidates.  Ops per query drop by another
~n_lists/n_probe on top of ICQ's crude-test pruning; the paper's
Average-Ops metric generalizes to

    ops = coarse_scan (n_lists dots) / n
          + probed_frac * (|K_fast| + pass_rate * (K - |K_fast|))

The batched engine (vs the retired per-query ``lax.map`` formulation,
kept as ``kernels/ref.py::ivf_two_step_search_looped``):

  1. coarse-probe the whole query block at once: one (nq, n_lists)
     distance matmul + batched ``top_k`` -> probes (nq, n_probe);
  2. gather the padded candidate slab: ``lists[probes]`` flattens to
     (nq, nc = n_probe * max_len) global ids (-1 pad) and one codes
     gather yields (nq, nc, K) — *still packed* uint8; codes widen only
     at the LUT-sum / kernel boundary;
  3. run the batched crude -> eq. 2 -> refine pipeline over the slab:
     backend="jnp" mirrors ``flat.two_step_search`` (with the optional
     static ``refine_cap`` compaction), backend="pallas" reuses the
     (query-tile x candidate-tile) fused kernels over the gathered slab
     (``kernels/batched_search.py`` ivf_* variants).

Static shapes for TPU: lists are padded to the max list length (pad id
-1, masked) — the memory overhead is the classic IVF imbalance factor,
reported by ``build_ivf``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.index.base import (SearchResult, _int_acc_dtype, build_lut,
                              chunked_over_queries, dequantize_acc,
                              lut_sum, mask_filtered_ids, quantize_lut,
                              resolve_backend, resolve_lut_dtype)
# The slab search paths are compositions of the stage objects
# (DESIGN.md §13); stages lazily imports index modules inside method
# bodies, so this top-level import is cycle-free.
from repro.kernels.stages import (CrudeStage, RefineStage, ThresholdStage,
                                  widen_codes as _widen_slab)


class IVFIndex(NamedTuple):
    centroids: jnp.ndarray       # (n_lists, d)
    lists: jnp.ndarray           # (n_lists, max_len) int32 db ids, -1 pad
    list_lens: jnp.ndarray       # (n_lists,)
    imbalance: float             # max_len / (n / n_lists)


def _pack_buckets(buckets, n_lists: int, n: int) -> IVFIndex:
    """Lay per-list id buckets out as the padded (n_lists, max_len)
    slab — shared by ``build_ivf`` / ``ivf_assign`` / ``ivf_extend``.
    Bucket entries must already be global database ids in ascending
    order (assignment iterates ids in order, so they are)."""
    # max over bucket lengths is 0 when every bucket is empty (k-means
    # collapse / n_lists > n leaves stragglers); keep max_len >= 1 so
    # the padded layout stays well-formed with all-(-1) rows
    max_len = max(max((len(b) for b in buckets), default=0), 1)
    lists = np.full((n_lists, max_len), -1, np.int32)
    for l, b in enumerate(buckets):
        lists[l, : len(b)] = b
    lens = np.asarray([len(b) for b in buckets], np.int32)
    return IVFIndex(centroids=None, lists=jnp.asarray(lists),
                    list_lens=jnp.asarray(lens),
                    imbalance=float(max_len / max(n / n_lists, 1)))


def build_ivf(key, emb_db, n_lists: int, kmeans_iters: int = 20) -> IVFIndex:
    """Coarse k-means partition of ``emb_db`` into padded inverted lists.

    List entries are int32 *global database ids* (pad -1): gathering
    ``codes[lists[probes]]`` keeps the candidate codes in their stored
    packed dtype (uint8 for m <= 256) all the way to the LUT-sum /
    kernel boundary — the gather never widens.
    """
    from repro.core import codebooks as cb

    n = int(emb_db.shape[0])
    if n_lists < 1:
        raise ValueError(f"n_lists must be >= 1, got {n_lists}")
    if n == 0:
        raise ValueError("cannot build an IVF over an empty database")
    # k-means cannot seed more centroids than points: fit the real
    # count and pad the remaining rows with a far-away sentinel (huge
    # but finite, so probe distances stay ordered, never NaN) over
    # permanently empty lists
    k_eff = min(n_lists, n)
    cent, ids = cb.kmeans(key, emb_db, k_eff, iters=kmeans_iters)
    if k_eff < n_lists:
        pad = jnp.full((n_lists - k_eff, cent.shape[1]), 1e15,
                       cent.dtype)
        cent = jnp.concatenate([cent, pad], axis=0)
    ids_np = np.asarray(ids)
    buckets = [np.where(ids_np == l)[0] for l in range(n_lists)]
    return _pack_buckets(buckets, n_lists, n)._replace(centroids=cent)


def ivf_assign(centroids, emb_db) -> IVFIndex:
    """Inverted lists from *fixed* coarse centroids: assign every
    ``emb_db`` row to its nearest centroid.  The from-scratch
    counterpart of ``ivf_extend`` — ``build_ivf(key, e1, L)`` then
    ``ivf_extend``-ing e2 yields exactly
    ``ivf_assign(ivf.centroids, concat(e1, e2))`` (DESIGN.md §9)."""
    from repro.core import codebooks as cb

    n = int(emb_db.shape[0])
    n_lists = centroids.shape[0]
    ids_np = np.asarray(cb.kmeans_assign(jnp.asarray(emb_db, jnp.float32),
                                         centroids))
    buckets = [np.where(ids_np == l)[0] for l in range(n_lists)]
    return _pack_buckets(buckets, n_lists, n)._replace(centroids=centroids)


def ivf_extend(ivf: IVFIndex, new_emb, start_id: int) -> IVFIndex:
    """Route new points into the existing inverted lists — the IVF leg
    of ``Index.add`` (DESIGN.md §9).  Centroids stay fixed (no
    retraining); each new embedding is assigned to its nearest centroid
    and its global id (``start_id + row``) appended to that list, with
    the padded slab re-laid-out (max_len grows as needed).  Appending
    preserves ascending id order per list, so the result is identical
    to ``ivf_assign`` over the concatenated embeddings."""
    from repro.core import codebooks as cb

    n_lists = ivf.lists.shape[0]
    new_ids = np.asarray(cb.kmeans_assign(
        jnp.asarray(new_emb, jnp.float32), ivf.centroids))
    lists_np = np.asarray(ivf.lists)
    lens_np = np.asarray(ivf.list_lens)
    buckets = [lists_np[l, : lens_np[l]] for l in range(n_lists)]
    for l in range(n_lists):
        extra = start_id + np.where(new_ids == l)[0].astype(np.int32)
        if extra.size:
            buckets[l] = np.concatenate([buckets[l], extra])
    n = start_id + int(new_emb.shape[0])
    return _pack_buckets(buckets, n_lists, n)._replace(
        centroids=ivf.centroids)


# -------------------------------------------------------------- engines ----

def coarse_probe(qs, centroids, n_probe: int):
    """Nearest-``n_probe`` centroid ids for a query block: one (nq,
    n_lists) distance matmul + batched top_k.  Returns (nq, n_probe)."""
    d2c = (jnp.sum(jnp.square(centroids), -1)[None, :]
           - 2.0 * qs @ centroids.T)                     # + ||q||^2 const
    _, probes = jax.lax.top_k(-d2c, n_probe)
    return probes


def ivf_list_codes(ivf: "IVFIndex", codes):
    """Move the packed codes *inside* the inverted lists: one padded
    (n_lists, max_len, K) slab in the stored dtype (pad rows repeat
    codes[0]; validity rides on the id slab).  Serving then gathers
    contiguous list rows per probe instead of scattered database rows —
    measurably faster and the layout the sharded engine serves from."""
    return jnp.take(codes, jnp.maximum(ivf.lists, 0), axis=0)


def gather_candidates(probes, lists, codes, topk: int, list_codes=None):
    """Flatten the probed lists into the per-query candidate slab.

    Returns (cand_ids (nq, nc), valid (nq, nc), cand_codes (nq, nc, K)
    in the *stored* packed dtype).  ``list_codes`` (from
    ``ivf_list_codes``) switches the codes gather to contiguous list
    rows; values are identical either way.  The slab is right-padded
    with invalid columns up to ``topk`` so downstream top_k calls always
    have enough columns.
    """
    nq = probes.shape[0]
    cand_ids = lists[probes].reshape(nq, -1)             # (nq, nc)
    if list_codes is not None:
        cand_codes = list_codes[probes].reshape(
            nq, cand_ids.shape[1], -1)                   # contiguous rows
    if cand_ids.shape[1] < topk:                         # tiny-slab guard
        pad = topk - cand_ids.shape[1]
        cand_ids = jnp.pad(cand_ids, ((0, 0), (0, pad)),
                           constant_values=-1)
    valid = cand_ids >= 0
    safe = jnp.where(valid, cand_ids, 0)
    if list_codes is None:
        cand_codes = jnp.take(codes, safe, axis=0)       # packed dtype kept
    elif cand_codes.shape[1] < cand_ids.shape[1]:
        cand_codes = jnp.pad(
            cand_codes,
            ((0, 0), (0, cand_ids.shape[1] - cand_codes.shape[1]), (0, 0)))
    return cand_ids, valid, cand_codes


def _slab_codes(cand_codes, k: int, code_bits: int):
    """Codebook k's codes from the candidate slab, widened to int32.
    Under ``code_bits=4`` the slab stays nibble-packed — the byte column
    is gathered once and the right nibble shifted out (DESIGN.md §12)."""
    if code_bits == 4:
        byte = cand_codes[:, :, k // 2].astype(jnp.int32)
        return (byte >> (4 * (k % 2))) & 0xF
    return cand_codes[:, :, k].astype(jnp.int32)


def _ivf_bootstrap_threshold(luts, crude, cand_codes, topk: int, sigma,
                             fast=None, code_bits: int = 8):
    """Eq. 2 threshold over the candidate slab — kept as the historical
    entry point; the arithmetic lives in
    ``kernels.stages.ThresholdStage.from_dense_slab``.  With ``fast``
    given (the quantized-crude path) the candidates' full distances are
    quantized-crude + exact-slow — the decomposition the fused kernels
    use — so jnp and pallas bootstrap identical thresholds under
    ``lut_dtype="int8"``."""
    stage = ThresholdStage(topk=topk, quantized=fast is not None,
                           code_bits=code_bits)
    return stage.from_dense_slab(luts, cand_codes, crude, fast, sigma)


def _ivf_crude_scores(luts, cand_codes, valid, fast, *,
                      quantized: bool, need_slow: bool,
                      code_bits: int = 8):
    """Crude (and optionally slow) LUT sums over the candidate slab —
    the shared scoring core of the full jnp engine and the crude-only
    floor (so the two are bitwise-identical by construction).

    One unrolled pass over the K (static, small) codebooks feeds both
    accumulators via per-codebook (nq, nc) gathers — never
    materializing the (nq, K, nc) parts tensor (which blows the cache
    at serving slab sizes) or a transposed codes copy; masking the
    gathered value == masking the LUT before the gather.  Returns
    (crude (nq, nc) with invalid +inf, slow (nq, nc))."""
    fvals = fast.astype(luts.dtype)                          # (K,)
    K = luts.shape[1]
    nq, nc = valid.shape
    slow = jnp.zeros((nq, nc), luts.dtype)
    if quantized:
        # int8 crude accumulation (DESIGN.md §8): masked codebooks are
        # zeroed in the table, the narrow integer sum skips them, one
        # affine rescale recovers true-distance units (ordered exactly
        # like the fused kernel's dequant)
        qlut = quantize_lut(luts, fast)
        acc = jnp.zeros((nq, nc), _int_acc_dtype(K))
        for k in range(K):
            ck = _slab_codes(cand_codes, k, code_bits)
            acc = acc + jnp.take_along_axis(qlut.q[:, k, :], ck,
                                            axis=1).astype(acc.dtype)
            if need_slow:
                v = jnp.take_along_axis(luts[:, k, :], ck, axis=1)
                slow = slow + (1.0 - fvals[k]) * v
        crude = dequantize_acc(qlut, acc, fast)
    else:
        crude = jnp.zeros((nq, nc), luts.dtype)
        for k in range(K):
            v = jnp.take_along_axis(
                luts[:, k, :], _slab_codes(cand_codes, k, code_bits), axis=1)
            crude = crude + fvals[k] * v
            if need_slow:
                slow = slow + (1.0 - fvals[k]) * v
    return jnp.where(valid, crude, jnp.inf), slow


def _ivf_crude_phase(qs, env, *, topk: int, n_probe: int, backend: str,
                     block_q: int = 8, block_n: int = 128, interpret=None,
                     quantized: bool = False, code_bits: int = 8,
                     refine_cap: Optional[int] = None,
                     has_filter: bool = False):
    """Crude half of the IVF two-step over one query tile: probe +
    gather + ``CrudeStage.slab``.  Returns the inter-phase carry
    ``(luts, crude, cand_vals, cand_pos, slow, cand_codes, safe,
    valid)`` — unused slots are None per backend (jnp defers the crude
    top-k to the bootstrap; pallas defers the slow sums to the fused
    refine kernel).  The refine phase is the carry's last reader, so
    the pipelined executor donates it (DESIGN.md §13)."""
    luts = build_lut(qs, env["C"])                       # (nq, K, m)
    probes = coarse_probe(qs, env["centroids"], n_probe)
    cand_ids, valid, cand_codes = gather_candidates(
        probes, env["lists"], env["codes"], topk, env["list_codes"])
    safe = jnp.where(valid, cand_ids, 0)
    stage = CrudeStage(backend=backend, topk=topk, block_q=block_q,
                       block_n=block_n, interpret=interpret,
                       quantized=quantized, code_bits=code_bits)
    if backend == "pallas":
        out = stage.slab(cand_codes, cand_ids, valid, luts, env["fast"])
        return (luts, out.crude, out.cand_vals, out.cand_idx, None,
                cand_codes, safe, valid)
    pred = env["pred"] if has_filter else None
    if pred is not None:
        # filtered rows score +inf crude: they can't pass eq. 2, can't
        # set the bootstrap threshold, and rank last
        valid = valid & pred[safe]
    out = stage.slab(cand_codes, cand_ids, valid, luts, env["fast"],
                     need_slow=refine_cap is None)
    return (luts, out.crude, None, None, out.slow, cand_codes, safe,
            valid)


def _ivf_refine_phase(carry, env, *, topk: int, backend: str,
                      block_q: int = 8, block_n: int = 128, interpret=None,
                      quantized: bool = False, code_bits: int = 8,
                      refine_cap: Optional[int] = None,
                      has_filter: bool = False):
    """Threshold bootstrap + refine over the crude carry.  Returns (ids
    (nq,topk), dist (nq,topk), n_cand (nq,), n_pass (nq,)).  The
    optional jnp ``refine_cap`` compaction re-ranks only the ``cap``
    best survivors by one full-table sum (the exact historical
    arithmetic, inline — it is a carry consumer, not a stage)."""
    luts, crude, cand_vals, cand_pos, slow, cand_codes, safe, valid = carry
    fast, sigma = env["fast"], env["sigma"]
    tstage = ThresholdStage(topk=topk, quantized=quantized,
                            code_bits=code_bits)
    rstage = RefineStage(backend=backend, topk=topk, block_q=block_q,
                         block_n=block_n, interpret=interpret,
                         code_bits=code_bits)
    n_cand = jnp.sum(valid.astype(jnp.float32), axis=1)
    if backend == "pallas":
        thr = tstage.from_slab_candidates(luts, cand_codes, cand_vals,
                                          cand_pos, fast, sigma)
        ids, dist, passed = rstage.slab(cand_codes, luts, crude, thr,
                                        fast, safe)
        n_pass = jnp.sum(passed.astype(jnp.float32), axis=1)
        return ids, dist, n_cand, n_pass
    pred = env["pred"] if has_filter else None
    thr = tstage.from_dense_slab(luts, cand_codes, crude,
                                 fast if quantized else None, sigma)
    passed = crude < thr[:, None]                        # invalid->inf->F
    if refine_cap is None:
        ids, dist, _ = rstage.slab(cand_codes, luts, crude, thr, fast,
                                   safe, slow=slow, pred=pred)
    else:
        # clamp into [topk, nc]: the slab is padded to >= topk columns
        cap = min(max(refine_cap, topk), crude.shape[1])
        masked = jnp.where(passed, crude, jnp.inf)
        neg_s, surv = jax.lax.top_k(-masked, cap)        # slab positions
        alive = jnp.isfinite(-neg_s)
        surv_codes = jnp.take_along_axis(cand_codes, surv[:, :, None],
                                         axis=1)         # (nq, cap, K)
        full_surv = lut_sum(luts, _widen_slab(surv_codes, luts.shape[1],
                                              code_bits))
        ranked = jnp.where(alive, full_surv, jnp.inf)
        neg, cpos = jax.lax.top_k(-ranked, topk)
        pos = jnp.take_along_axis(surv, cpos, axis=1)
        ids = jnp.take_along_axis(safe, pos, axis=1)
        dist = -neg
        if pred is not None:
            ids = mask_filtered_ids(ids, dist)
    n_pass = jnp.sum(passed.astype(jnp.float32), axis=1)
    return ids, dist, n_cand, n_pass


def _ivf_block_jnp(qs, codes, C, fast, sigma, topk: int, centroids, lists,
                   n_probe: int, refine_cap: Optional[int],
                   list_codes=None, quantized: bool = False,
                   code_bits: int = 8, pred=None):
    """Batched IVF two-step over one query block — the sequential
    composition of the crude and refine phases.  Returns (ids
    (nq,topk), dist (nq,topk), n_cand (nq,), n_pass (nq,))."""
    env = {"codes": codes, "C": C, "fast": fast, "sigma": sigma,
           "centroids": centroids, "lists": lists,
           "list_codes": list_codes, "pred": pred}
    crude_fn, refine_fn = ivf_phase_fns(
        topk=topk, n_probe=n_probe, backend="jnp", quantized=quantized,
        code_bits=code_bits, refine_cap=refine_cap,
        has_filter=pred is not None)
    return refine_fn(crude_fn(qs, env), env)


def _ivf_block_pallas(qs, codes, C, fast, sigma, topk: int, centroids,
                      lists, n_probe: int, block_q: int, block_n: int,
                      interpret, list_codes=None, quantized: bool = False,
                      code_bits: int = 8):
    """Fused-kernel batched IVF: the (query-tile x candidate-tile)
    kernels from ``kernels/batched_search.py`` sweep the gathered slab
    (phase-1 crude + running top-k, then fused eq. 2 + refine + top-k
    merge); the tiny threshold bootstrap stays in jnp.  ``quantized``
    feeds phase 1 int8 tables (dequantized in-kernel); phase 2 keeps
    the exact f32 slow tables either way."""
    env = {"codes": codes, "C": C, "fast": fast, "sigma": sigma,
           "centroids": centroids, "lists": lists,
           "list_codes": list_codes, "pred": None}
    crude_fn, refine_fn = ivf_phase_fns(
        topk=topk, n_probe=n_probe, backend="pallas", block_q=block_q,
        block_n=block_n, interpret=interpret, quantized=quantized,
        code_bits=code_bits)
    return refine_fn(crude_fn(qs, env), env)


def ivf_ops_result(ids, dist, n_cand, n_pass, *, n: int, n_lists: int,
                   K, kf) -> SearchResult:
    """Fold per-query candidate/pass counts into the generalized
    Average-Ops accounting shared by every IVF engine."""
    probed_frac = jnp.mean(n_cand) / n
    pass_rate = jnp.mean(n_pass) / jnp.maximum(jnp.mean(n_cand), 1.0)
    coarse = n_lists / n                                 # dots per point
    avg_ops = coarse * K / 2 + probed_frac * (kf + pass_rate * (K - kf))
    # (coarse dots cost ~d mults each ~ K/2 LUT-adds-equivalent at m=2d)
    return SearchResult(ids, dist, avg_ops, pass_rate)


def ivf_two_step_search(queries, codes, C, structure, ivf: IVFIndex,
                        topk: int, n_probe: int, *, backend: str = "auto",
                        block_q: int = 8, block_n: int = 128,
                        interpret=None, query_chunk: Optional[int] = None,
                        refine_cap: Optional[int] = None, list_codes=None,
                        lut_dtype: str = "f32", code_bits: int = 8,
                        filter=None):
    """Batched IVF + ICQ two-step.  Returns SearchResult with the
    generalized ops accounting (see module docstring).

    ``list_codes`` (optional, from ``ivf_list_codes``) serves from the
    in-list codes slab — same results, faster gather.  ``lut_dtype``
    ("f32" | "int8") selects the crude-pass table precision (DESIGN.md
    §8); the refine pass is always f32.  ``code_bits=4`` serves from
    nibble-packed codes/list_codes (DESIGN.md §12) — the fast-scan slab
    variant — with identical rankings to the 8-bit layout.  ``filter``:
    optional (n,) boolean row predicate (jnp engine only); excluded
    rows never appear in results — absent slots are id -1 / dist
    +inf."""
    from repro.index.flat import _check_fastscan_geometry, _check_filter

    K = C.shape[0]
    code_bits = _check_fastscan_geometry(code_bits, C.shape[1])
    fast = structure.fast_mask
    sigma = structure.sigma
    kf = jnp.sum(fast.astype(jnp.float32))
    n_lists = ivf.lists.shape[0]
    n = codes.shape[0]
    if not 1 <= n_probe <= n_lists:
        raise ValueError(f"n_probe={n_probe} outside [1, {n_lists}]")
    be = resolve_backend(backend)
    quantized = resolve_lut_dtype(lut_dtype) == "int8"
    pred = _check_filter(filter, n, be)

    if be == "pallas":
        if refine_cap is not None:
            raise ValueError("refine_cap compaction requires backend='jnp'"
                             " (the fused kernels bound phase-2 work with"
                             " the in-kernel top-k merge instead)")
        fn = functools.partial(_ivf_block_pallas, codes=codes, C=C,
                               fast=fast, sigma=sigma, topk=topk,
                               centroids=ivf.centroids, lists=ivf.lists,
                               n_probe=n_probe, block_q=block_q,
                               block_n=block_n, interpret=interpret,
                               list_codes=list_codes, quantized=quantized,
                               code_bits=code_bits)
    else:
        fn = functools.partial(_ivf_block_jnp, codes=codes, C=C, fast=fast,
                               sigma=sigma, topk=topk,
                               centroids=ivf.centroids, lists=ivf.lists,
                               n_probe=n_probe, refine_cap=refine_cap,
                               list_codes=list_codes, quantized=quantized,
                               code_bits=code_bits, pred=pred)
    ids, dist, n_cand, n_pass = chunked_over_queries(fn, queries,
                                                     query_chunk)
    return ivf_ops_result(ids, dist, n_cand, n_pass, n=n, n_lists=n_lists,
                          K=K, kf=kf)


def _ivf_crude_only_phase(qs, env, *, topk: int, n_probe: int,
                          backend: str, block_q: int = 8,
                          block_n: int = 128, interpret=None,
                          quantized: bool = False, code_bits: int = 8,
                          has_filter: bool = False):
    """Single-phase crude-only IVF ranking (the degradation ladder's
    floor): probe + gather + ``CrudeStage.slab`` + top-k, skipping
    eq. 2 and refinement — structurally the full path with its refine
    phase dropped, so the ranking is exactly the crude top-k the full
    path bootstraps its eq. 2 candidates from (same backend)."""
    luts = build_lut(qs, env["C"])
    probes = coarse_probe(qs, env["centroids"], n_probe)
    cand_ids, valid, cand_codes = gather_candidates(
        probes, env["lists"], env["codes"], topk, env["list_codes"])
    safe = jnp.where(valid, cand_ids, 0)
    stage = CrudeStage(backend=backend, topk=topk, block_q=block_q,
                       block_n=block_n, interpret=interpret,
                       quantized=quantized, code_bits=code_bits)
    if backend == "pallas":
        out = stage.slab(cand_codes, cand_ids, valid, luts, env["fast"])
        pos_safe = jnp.where(jnp.isfinite(out.cand_vals), out.cand_idx, 0)
        ids = jnp.take_along_axis(safe, pos_safe, axis=1)
        n_cand = jnp.sum(valid.astype(jnp.float32), axis=1)
        return ids, out.cand_vals, n_cand, jnp.zeros_like(n_cand)
    pred = env["pred"] if has_filter else None
    if pred is not None:
        valid = valid & pred[safe]
    out = stage.slab(cand_codes, cand_ids, valid, luts, env["fast"],
                     need_slow=False)
    neg_c, pos = jax.lax.top_k(-out.crude, topk)
    ids = jnp.take_along_axis(safe, pos, axis=1)
    if pred is not None:
        ids = mask_filtered_ids(ids, -neg_c)
    n_cand = jnp.sum(valid.astype(jnp.float32), axis=1)
    return ids, -neg_c, n_cand, jnp.zeros_like(n_cand)


def _ivf_crude_block_jnp(qs, codes, C, fast, topk: int, centroids, lists,
                         n_probe: int, list_codes=None,
                         quantized: bool = False, code_bits: int = 8,
                         pred=None):
    """Crude-only IVF ranking over one query block (jnp)."""
    env = {"codes": codes, "C": C, "fast": fast, "sigma": None,
           "centroids": centroids, "lists": lists,
           "list_codes": list_codes, "pred": pred}
    crude_fn, _ = ivf_phase_fns(
        topk=topk, n_probe=n_probe, backend="jnp", quantized=quantized,
        code_bits=code_bits, crude_only=True,
        has_filter=pred is not None)
    return crude_fn(qs, env)


def _ivf_crude_block_pallas(qs, codes, C, fast, topk: int, centroids,
                            lists, n_probe: int, block_q: int, block_n: int,
                            interpret, list_codes=None,
                            quantized: bool = False, code_bits: int = 8):
    """Crude-only IVF via the phase-1 kernel: ``ivf_crude_topk``'s
    running top-k over the slab *is* the crude ranking; phase 2 is
    skipped.  ``code_bits=4`` streams the nibble-packed slab through the
    fast-scan variant."""
    env = {"codes": codes, "C": C, "fast": fast, "sigma": None,
           "centroids": centroids, "lists": lists,
           "list_codes": list_codes, "pred": None}
    crude_fn, _ = ivf_phase_fns(
        topk=topk, n_probe=n_probe, backend="pallas", block_q=block_q,
        block_n=block_n, interpret=interpret, quantized=quantized,
        code_bits=code_bits, crude_only=True)
    return crude_fn(qs, env)


# ------------------------------------------------------ phase factories ----

def ivf_phase_env(codes, C, structure, ivf: IVFIndex, *, list_codes=None,
                  pred=None):
    """The borrowed-operand environment shared by every IVF phase — the
    arrays a ``PipelinedSearch`` executor may alias across query tiles
    (the phases only read them)."""
    return {"codes": codes, "C": C, "fast": structure.fast_mask,
            "sigma": structure.sigma, "centroids": ivf.centroids,
            "lists": ivf.lists, "list_codes": list_codes, "pred": pred}


def ivf_phase_fns(*, topk: int, n_probe: int, backend: str,
                  block_q: int = 8, block_n: int = 128, interpret=None,
                  quantized: bool = False, code_bits: int = 8,
                  refine_cap: Optional[int] = None,
                  crude_only: bool = False, has_filter: bool = False):
    """The IVF search split at the crude/refine boundary: returns
    ``(crude_fn, refine_fn)`` taking ``(qs|carry, env)`` — the phase
    pair both the sequential blocks above and the pipelined executor
    compose.  ``crude_only`` returns the single-phase floor as
    ``(crude_fn, None)``."""
    common = dict(topk=topk, backend=backend, block_q=block_q,
                  block_n=block_n, interpret=interpret,
                  quantized=quantized, code_bits=code_bits,
                  has_filter=has_filter)
    if crude_only:
        return (functools.partial(_ivf_crude_only_phase, n_probe=n_probe,
                                  **common), None)
    return (functools.partial(_ivf_crude_phase, n_probe=n_probe,
                              refine_cap=refine_cap, **common),
            functools.partial(_ivf_refine_phase, refine_cap=refine_cap,
                              **common))


def ivf_crude_search(queries, codes, C, structure, ivf: IVFIndex,
                     topk: int, n_probe: int, *, backend: str = "auto",
                     block_q: int = 8, block_n: int = 128, interpret=None,
                     query_chunk: Optional[int] = None, list_codes=None,
                     lut_dtype: str = "f32", code_bits: int = 8,
                     filter=None):
    """The IVF rung of the degradation ladder's crude floor
    (docs/robustness.md): probe + crude-only ranking over the candidate
    slab.  Bitwise-identical ids/values to the crude top-k the full
    path computes internally on the same backend.  ``avg_ops`` drops
    the pass-rate term (nothing refined).  ``code_bits=4`` serves the
    floor straight from the nibble-packed slab."""
    from repro.index.flat import _check_fastscan_geometry, _check_filter

    K = C.shape[0]
    code_bits = _check_fastscan_geometry(code_bits, C.shape[1])
    fast = structure.fast_mask
    kf = jnp.sum(fast.astype(jnp.float32))
    n_lists = ivf.lists.shape[0]
    n = codes.shape[0]
    if not 1 <= n_probe <= n_lists:
        raise ValueError(f"n_probe={n_probe} outside [1, {n_lists}]")
    be = resolve_backend(backend)
    quantized = resolve_lut_dtype(lut_dtype) == "int8"
    pred = _check_filter(filter, n, be)

    if be == "pallas":
        fn = functools.partial(_ivf_crude_block_pallas, codes=codes, C=C,
                               fast=fast, topk=topk,
                               centroids=ivf.centroids, lists=ivf.lists,
                               n_probe=n_probe, block_q=block_q,
                               block_n=block_n, interpret=interpret,
                               list_codes=list_codes, quantized=quantized,
                               code_bits=code_bits)
    else:
        fn = functools.partial(_ivf_crude_block_jnp, codes=codes, C=C,
                               fast=fast, topk=topk,
                               centroids=ivf.centroids, lists=ivf.lists,
                               n_probe=n_probe, list_codes=list_codes,
                               quantized=quantized, code_bits=code_bits,
                               pred=pred)
    ids, dist, n_cand, n_pass = chunked_over_queries(fn, queries,
                                                     query_chunk)
    return ivf_ops_result(ids, dist, n_cand, n_pass, n=n, n_lists=n_lists,
                          K=K, kf=kf)


# --------------------------------------------------------------- index ----

@dataclasses.dataclass(frozen=True)
class IVFTwoStep:
    """IVF-pruned ICQ two-step index: coarse partition probe + batched
    candidate-slab two-step."""
    codes: jnp.ndarray                  # (n, K) packed ((n, ceil(K/2))
                                        # nibble-packed at code_bits=4)
    C: jnp.ndarray                      # (K, m, d)
    structure: object                   # core.icq.ICQStructure
    ivf: IVFIndex
    n_probe: int = 8
    topk: int = 50
    backend: str = "auto"
    block_q: int = 8
    block_n: int = 128
    interpret: Optional[bool] = None
    query_chunk: Optional[int] = None
    refine_cap: Optional[int] = None
    lut_dtype: str = "f32"
    code_bits: int = 8
    list_codes: Optional[jnp.ndarray] = None     # (n_lists, max_len, K)
    pipeline: str = "off"                        # "off" | "tiles" | "auto"
    pipeline_tile: Optional[int] = None

    @classmethod
    def build(cls, codes, C, structure, *, emb_db, key=None,
              n_lists: int = 64, kmeans_iters: int = 20,
              **opts) -> "IVFTwoStep":
        """Fit the coarse quantizer over ``emb_db`` and assemble the
        index (codes slab moved inside the lists).  ``emb_db`` must be
        the embeddings the codes encode."""
        key = jax.random.PRNGKey(0) if key is None else key
        ivf = build_ivf(key, emb_db, n_lists, kmeans_iters=kmeans_iters)
        return cls(codes=codes, C=C, structure=structure, ivf=ivf,
                   list_codes=ivf_list_codes(ivf, codes), **opts)

    def search(self, queries, topk: Optional[int] = None, *,
               filter=None) -> SearchResult:
        k = topk if topk is not None else self.topk
        if self.pipeline != "off":
            from repro.index.pipelined import maybe_pipelined
            res = maybe_pipelined(self, queries, k, filter=filter)
            if res is not None:
                return res
        return ivf_two_step_search(
            queries, self.codes, self.C, self.structure, self.ivf,
            k, self.n_probe,
            backend=self.backend, block_q=self.block_q,
            block_n=self.block_n, interpret=self.interpret,
            query_chunk=self.query_chunk, refine_cap=self.refine_cap,
            list_codes=self.list_codes, lut_dtype=self.lut_dtype,
            code_bits=self.code_bits, filter=filter)

    def search_crude(self, queries, topk: Optional[int] = None,
                     n_probe: Optional[int] = None, *,
                     filter=None) -> SearchResult:
        """Crude-only floor (docs/robustness.md): probe + crude ranking
        with no refinement, bitwise-identical to the full path's
        internal crude top-k on the same backend.  ``n_probe`` lets the
        ladder's "probes" rung reuse this entry with a reduced probe
        count."""
        k = topk if topk is not None else self.topk
        if self.pipeline != "off":
            from repro.index.pipelined import maybe_pipelined
            res = maybe_pipelined(self, queries, k, filter=filter,
                                  crude_only=True, n_probe=n_probe)
            if res is not None:
                return res
        return ivf_crude_search(
            queries, self.codes, self.C, self.structure, self.ivf, k,
            n_probe if n_probe is not None else self.n_probe,
            backend=self.backend, block_q=self.block_q,
            block_n=self.block_n, interpret=self.interpret,
            query_chunk=self.query_chunk, list_codes=self.list_codes,
            lut_dtype=self.lut_dtype, code_bits=self.code_bits,
            filter=filter)

    def add(self, new_vectors, *, icm_iters: int = 3,
            encode_backend: str = "auto",
            point_chunk: Optional[int] = 8192) -> "IVFTwoStep":
        """Encode ``new_vectors`` ((n_new, d) embeddings) through the
        tiled engine and route them into the owning inverted lists —
        incremental build, coarse centroids fixed, no retraining
        (DESIGN.md §9).  New rows get ids [n, n + n_new); the in-list
        codes slab is rebuilt when the index serves from one.  Search
        results are identical to a from-scratch index over the
        concatenated embeddings with the same centroids
        (``ivf_assign``)."""
        from repro.index.flat import _encode_new_rows

        new = _encode_new_rows(new_vectors, self.C, self.codes.dtype,
                               icm_iters=icm_iters,
                               encode_backend=encode_backend,
                               point_chunk=point_chunk,
                               code_bits=self.code_bits)
        codes = jnp.concatenate([self.codes, new], axis=0)
        ivf = ivf_extend(self.ivf, new_vectors,
                         start_id=self.codes.shape[0])
        lc = (ivf_list_codes(ivf, codes) if self.list_codes is not None
              else None)
        return dataclasses.replace(self, codes=codes, ivf=ivf,
                                   list_codes=lc)

    def shard(self, mesh):
        from repro.index.sharded import ShardedIVFTwoStep
        return ShardedIVFTwoStep(self, mesh)
