"""Flat (exhaustive) indexes: one-step ADC and the ICQ two-step engine.

Both scan every database point; ``TwoStep`` prunes refinement work with
the paper's eq. 2 margin test.  The engine implementations moved here
from ``core/search.py`` (now a thin re-export) as part of the unified
index layer (DESIGN.md §7); behavior and backends are unchanged:

  backend="jnp"     fully vectorized reference — batched ``build_lut``,
                    one ``take_along_axis`` gather per LUT sum, batched
                    ``top_k`` over the whole query block (no per-query
                    ``lax.map``).  Optionally chunked over queries
                    (``query_chunk``) to bound the (nq, n) working set.
  backend="pallas"  the fused (query-tile x point-tile) kernels in
                    ``kernels/batched_search.py``: LUT tiles pinned in
                    VMEM, each codes tile streamed from HBM once per
                    query tile, eq. 2 test + slow-codebook refine +
                    top-k merge fused in-kernel.
  backend="auto"    "pallas" on TPU backends, "jnp" elsewhere.

``two_step_search`` folds the static survivor compaction that used to be
a separate entry (``two_step_search_compact``) into the dispatch as the
``refine_cap`` engine option: at most ``refine_cap`` best-crude
survivors per query are gathered and refined — a static-shape bound on
phase-2 work (jnp engine only; the fused kernels bound phase-2 memory
with the in-kernel top-k merge instead).

Database codes are stored packed (uint8 for m <= 256, core.encode.
pack_codes) and widened to int32 only at the engine boundary — 4x less
HBM traffic per streamed codes tile.

"Average Ops" — the paper's speed metric (Figs. 1-5) — counts LUT adds
per point:  |K_fast| + pass_rate * (K - |K_fast|), vs always-K for
ADC baselines.

``lut_dtype="int8"`` (DESIGN.md §8) runs the crude pass on per-query
affine-quantized tables (``base.quantize_lut``): integer accumulation,
one rescale back to true-distance units.  The refine/slow pass always
stays float32 — eq. 2's exact re-ranking is untouched; quantization
only perturbs which points pass the margin test and the crude component
of reported distances (bounded by |K_fast| * scale / 2 per point).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.index.base import (SearchResult, as_filter, build_lut,
                              chunked_over_queries, lut_sum,
                              mask_filtered_ids, resolve_backend,
                              resolve_code_bits, resolve_lut_dtype)
# The search paths are compositions of the stage objects (DESIGN.md
# §13); the stage module lazily imports index.base inside method
# bodies, so this top-level import is cycle-free.
from repro.kernels.stages import (CrudeStage, RefineStage, ThresholdStage,
                                  widen_codes as _widen_codes)


# -------------------------------------------------------------- engines ----

def _check_fastscan_geometry(code_bits: int, m: int):
    """``code_bits=4`` stores two codes per byte, so every code must be
    a nibble: m <= 16 codewords per codebook (DESIGN.md §12)."""
    code_bits = resolve_code_bits(code_bits)
    if code_bits == 4 and m > 16:
        raise ValueError(f"code_bits=4 requires codebook_size <= 16 "
                         f"codewords (4-bit codes), got m={m}")
    return code_bits


def _check_filter(filter, n: int, backend: str):
    """Resolve the per-row predicate of a filtered search (docs/api.md).

    Filtered search is a jnp-engine capability — the fused kernels
    bound their candidate sets in-kernel and cannot drop rows by
    predicate (mirroring the ``refine_cap`` restriction), so
    ``backend="pallas"`` + ``filter`` raises by name."""
    if filter is None:
        return None
    if backend == "pallas":
        raise ValueError("filtered search requires backend='jnp' (the "
                         "fused kernels cannot mask rows by predicate; "
                         "like refine_cap, filter is a jnp-engine "
                         "option)")
    return as_filter(filter, n)

def _adc_block(qs, env, *, topk: int, backend: str, block_q: int = 64,
               block_n: int = 512, interpret=None, quantized: bool = False,
               code_bits: int = 8, has_filter: bool = False):
    """One-step ADC over one query block: a single ``CrudeStage`` with
    ``fast=None`` (the full table is the crude pass) — there is no
    threshold or refine stage to compose.  env: {"codes", "C"[, "pred"]}.
    Returns (ids (nq, topk), dist (nq, topk))."""
    pred = env["pred"] if has_filter else None
    stage = CrudeStage(backend=backend, topk=topk, block_q=block_q,
                       block_n=block_n, interpret=interpret,
                       quantized=quantized, code_bits=code_bits,
                       want_crude=False)
    luts = build_lut(qs, env["C"])
    if backend == "pallas":
        # codes stay packed into the kernel (widened per-tile in VMEM)
        out = stage(env["codes"], luts, None)
        return out.cand_idx, out.cand_vals
    dist = stage(env["codes"], luts, None, pred=pred).crude   # (nq, n)
    neg, ids = jax.lax.top_k(-dist, topk)
    if pred is not None:
        ids = mask_filtered_ids(ids, -neg)
    return ids, -neg


def adc_search(queries, codes, C, topk: int, *, backend: str = "auto",
               block_q: int = 64, block_n: int = 512, interpret=None,
               query_chunk: Optional[int] = None, lut_dtype: str = "f32",
               code_bits: int = 8, filter=None):
    """Baseline one-step ADC: full K-codebook LUT sum for every point,
    batched over the whole query block.

    queries (nq, d) f32; codes (n, K) packed int — nibble-packed
    (n, ceil(K/2)) uint8 under ``code_bits=4`` (DESIGN.md §12); C
    (K, m, d) f32.  ``lut_dtype="int8"`` quantizes the whole table per
    query (no fast subset here — the one-step ranking itself becomes
    approximate, with per-point error <= K * scale / 2).

    ``filter``: optional (n,) bool per-row predicate (jnp engine only)
    — excluded rows never appear in results; slots with no eligible row
    left report id -1 at distance +inf."""
    K, m = C.shape[0], C.shape[1]
    be = resolve_backend(backend)
    quantized = resolve_lut_dtype(lut_dtype) == "int8"
    code_bits = _check_fastscan_geometry(code_bits, m)
    pred = _check_filter(filter, codes.shape[0], be)
    if be != "pallas" and code_bits != 4:
        codes = codes.astype(jnp.int32)              # widen packed codes
    env = {"codes": codes, "C": C, "pred": pred}
    fn = functools.partial(_adc_block, env=env, topk=topk, backend=be,
                           block_q=block_q, block_n=block_n,
                           interpret=interpret, quantized=quantized,
                           code_bits=code_bits, has_filter=pred is not None)
    idx, vals = chunked_over_queries(fn, queries, query_chunk)
    return SearchResult(idx, vals, jnp.asarray(float(K)), jnp.asarray(1.0))


# The two-step engine as a crude/refine phase pair (DESIGN.md §13).
# Each phase is a pure function of (queries | carry, env) where env is
# the borrowed index state {"codes", "C", "fast", "sigma"[, "pred"]};
# the carry between them is the owned intermediate buffer set
# (luts, crude, cand_vals, cand_idx) that the refine phase is the last
# reader of.  The sequential blocks below compose the two phases
# back-to-back; ``index/pipelined.py`` jits them separately (refine with
# ``donate_argnums`` on the carry) and overlaps crude(t+1) with
# refine(t) across query tiles.

def _flat_crude_phase(qs, env, *, topk: int, backend: str,
                      block_q: int = 64, block_n: int = 512,
                      interpret=None, quantized: bool = False,
                      code_bits: int = 8, has_filter: bool = False):
    """Phase 1: per-query LUTs + the crude pass.  Returns the carry
    (luts, crude, cand_vals, cand_idx) — the fused kernel also emits
    its running crude top-k; the dense jnp path defers the candidate
    top-k to the threshold bootstrap (cand_* = None).

    ``pred`` (filtered search, jnp): excluded rows get crude = +inf
    *before* the eq. 2 bootstrap, so they can neither become
    candidates, set the threshold, nor pass the margin test — recall is
    measured against the filtered oracle, not a post-hoc drop."""
    stage = CrudeStage(backend=backend, topk=topk, block_q=block_q,
                       block_n=block_n, interpret=interpret,
                       quantized=quantized, code_bits=code_bits)
    luts = build_lut(qs, env["C"])                       # (nq,K,m)
    with jax.named_scope("crude"):
        if backend == "pallas":
            out = stage(env["codes"], luts, env["fast"])
            return luts, out.crude, out.cand_vals, out.cand_idx
        pred = env["pred"] if has_filter else None
        out = stage(env["codes"], luts, env["fast"], pred=pred)
        return luts, out.crude, None, None


def _flat_refine_phase(carry, env, *, topk: int, backend: str,
                       block_q: int = 64, block_n: int = 512,
                       interpret=None, quantized: bool = False,
                       code_bits: int = 8,
                       refine_cap: Optional[int] = None,
                       has_filter: bool = False):
    """Phases 2+3: the eq. 2 threshold bootstrap and the refine pass.
    Consumes (donates) the crude-phase carry.  Returns (idx, dist,
    passed_frac (nq,)).

    The bootstrap formulation per path is preserved exactly: the dense
    jnp path ranks candidates from the crude matrix
    (``ThresholdStage.from_dense`` — quantized mode uses the
    crude + exact-slow decomposition the kernels share), the pallas
    path from the kernel's candidate list (``from_candidates``).
    ``refine_cap`` (jnp only) swaps the dense refine for the static
    survivor compaction: the refine_cap best crude survivors are
    gathered and re-ranked by full LUT sum (always exact f32 — under
    ``lut_dtype="int8"`` quantization only affects which points survive
    and their selection order)."""
    luts, crude, cand_vals, cand_idx = carry
    codes, fast, sigma = env["codes"], env["fast"], env["sigma"]
    pred = env["pred"] if has_filter else None
    tstage = ThresholdStage(topk=topk, quantized=quantized,
                            code_bits=code_bits)
    rstage = RefineStage(backend=backend, topk=topk, block_q=block_q,
                         block_n=block_n, interpret=interpret,
                         code_bits=code_bits)
    with jax.named_scope("threshold"):
        if backend == "pallas":
            thr = tstage.from_candidates(luts, codes, cand_vals, cand_idx,
                                         fast, sigma)
        else:
            thr = tstage.from_dense(luts, codes, crude, fast, sigma)
    if refine_cap is None:
        with jax.named_scope("refine"):
            idx, dist, passed = rstage(codes, luts, crude, thr, fast,
                                       pred=pred)
            return idx, dist, jnp.mean(passed.astype(jnp.float32), axis=1)
    # compact: best-crude survivors first, capped
    with jax.named_scope("refine"):
        passed = crude < thr[:, None]
        masked = jnp.where(passed, crude, jnp.inf)
        neg_s, surv = jax.lax.top_k(-masked, refine_cap)
        valid = jnp.isfinite(-neg_s)
        surv_codes = jnp.take(codes, surv, axis=0)       # (nq,cap,K)
        if code_bits == 4:
            surv_codes = _widen_codes(surv_codes, env["C"].shape[0],
                                      code_bits)
        full_surv = lut_sum(luts, surv_codes)
        ranked = jnp.where(valid, full_surv, jnp.inf)
        with jax.named_scope("merge"):
            neg, pos = jax.lax.top_k(-ranked, topk)
            idx = jnp.take_along_axis(surv, pos, axis=1)
            if pred is not None:
                idx = mask_filtered_ids(idx, -neg)
        return idx, -neg, jnp.mean(passed.astype(jnp.float32), axis=1)


def _two_step_block_jnp(qs, codes, C, fast, sigma, topk: int,
                        quantized: bool = False, code_bits: int = 8,
                        pred=None):
    """Vectorized two-step over one query block: the sequential
    composition of the crude and refine phases.  Returns
    (idx (nq,topk), dist (nq,topk), passed_frac (nq,))."""
    env = {"codes": codes, "C": C, "fast": fast, "sigma": sigma,
           "pred": pred}
    carry = _flat_crude_phase(qs, env, topk=topk, backend="jnp",
                              quantized=quantized, code_bits=code_bits,
                              has_filter=pred is not None)
    return _flat_refine_phase(carry, env, topk=topk, backend="jnp",
                              quantized=quantized, code_bits=code_bits,
                              has_filter=pred is not None)


def _two_step_block_compact(qs, codes, C, fast, sigma, topk: int,
                            refine_cap: int, quantized: bool = False,
                            code_bits: int = 8, pred=None):
    """Two-step with the static survivor compaction — the same phase
    pair with the capped refine tail (see ``_flat_refine_phase``)."""
    env = {"codes": codes, "C": C, "fast": fast, "sigma": sigma,
           "pred": pred}
    carry = _flat_crude_phase(qs, env, topk=topk, backend="jnp",
                              quantized=quantized, code_bits=code_bits,
                              has_filter=pred is not None)
    return _flat_refine_phase(carry, env, topk=topk, backend="jnp",
                              quantized=quantized, code_bits=code_bits,
                              refine_cap=refine_cap,
                              has_filter=pred is not None)


def _two_step_pallas(queries, codes, C, fast, sigma, topk: int,
                     block_q: int, block_n: int, interpret,
                     quantized: bool = False, code_bits: int = 8):
    """Fused-kernel two-step: phase-1 crude + candidate top-k in one
    kernel, tiny candidate refinement in jnp, fused phase-2 kernel —
    the same phase pair, pallas stages.  ``quantized`` feeds phase 1
    int8 tables (dequantized in-kernel); phase 2 keeps the exact f32
    slow tables either way."""
    env = {"codes": codes, "C": C, "fast": fast, "sigma": sigma,
           "pred": None}
    carry = _flat_crude_phase(queries, env, topk=topk, backend="pallas",
                              block_q=block_q, block_n=block_n,
                              interpret=interpret, quantized=quantized,
                              code_bits=code_bits)
    return _flat_refine_phase(carry, env, topk=topk, backend="pallas",
                              block_q=block_q, block_n=block_n,
                              interpret=interpret, quantized=quantized,
                              code_bits=code_bits)


def two_step_search(queries, codes, C, structure, topk: int, *,
                    backend: str = "auto", block_q: int = 64,
                    block_n: int = 512, interpret=None,
                    query_chunk: Optional[int] = None,
                    refine_cap: Optional[int] = None,
                    lut_dtype: str = "f32", code_bits: int = 8,
                    filter=None):
    """ICQ two-step search (eq. 2 crude test -> eq. 1 refinement),
    batched over the whole query block.

    structure:  core.icq.ICQStructure (xi, fast_mask, sigma).
    backend:    "jnp" | "pallas" | "auto" (pallas on TPU) — see module
                docstring; both produce identical rankings.
    code_bits:  8 (byte codes) | 4 (fast-scan mode, DESIGN.md §12:
                ``codes`` arrive nibble-packed (n, ceil(K/2)) uint8,
                requires codebook_size <= 16; rankings match the 8-bit
                path bitwise for either lut_dtype).
    refine_cap: optional static survivor compaction (jnp engine): at
                most this many best-crude survivors are refined.  Under
                lut_dtype="f32", semantically identical to the dense
                ranking whenever the survivor count <= refine_cap; a
                smaller cap is a quality/throughput dial for serving.
                Under "int8" the capped path re-ranks its survivors by
                *exact* f32 full distance while the dense path ranks by
                quantized-crude + exact-slow, so the two can differ on
                quantization-margin ties even with a sufficient cap
                (the capped ranking is the more exact of the two).
    lut_dtype:  "f32" (exact crude pass) | "int8" (per-query quantized
                crude tables, DESIGN.md §8).  The refine pass is always
                f32; both backends produce identical rankings for
                either dtype.
    filter:     optional (n,) bool per-row metadata predicate (jnp
                engine only, like refine_cap): excluded rows get crude
                +inf *before* the eq. 2 bootstrap — they can't become
                candidates, set the threshold, or appear in results;
                unfilled slots report id -1 at distance +inf.
    """
    K = C.shape[0]
    fast = structure.fast_mask
    sigma = structure.sigma
    kf = jnp.sum(fast.astype(jnp.float32))
    be = resolve_backend(backend)
    quantized = resolve_lut_dtype(lut_dtype) == "int8"
    code_bits = _check_fastscan_geometry(code_bits, C.shape[1])
    pred = _check_filter(filter, codes.shape[0], be)
    # nibble codes stay packed through both backends (the jnp blocks
    # unpack on the fly; the kernels unpack in-VMEM)
    codes_j = codes if code_bits == 4 else codes.astype(jnp.int32)

    if be == "pallas":
        if refine_cap is not None:
            raise ValueError("refine_cap compaction requires backend='jnp'"
                             " (the fused kernels bound phase-2 work with"
                             " the in-kernel top-k merge instead)")
        # codes stay packed into the kernels (widened per-tile in VMEM);
        # query_chunk bounds the dense (chunk, n) crude matrix here too
        fn = functools.partial(_two_step_pallas, codes=codes, C=C,
                               fast=fast, sigma=sigma, topk=topk,
                               block_q=block_q, block_n=block_n,
                               interpret=interpret, quantized=quantized,
                               code_bits=code_bits)
    elif refine_cap is not None:
        fn = functools.partial(_two_step_block_compact,
                               codes=codes_j, C=C,
                               fast=fast, sigma=sigma, topk=topk,
                               refine_cap=min(max(refine_cap, topk),
                                              codes.shape[0]),
                               quantized=quantized, code_bits=code_bits,
                               pred=pred)
    else:
        fn = functools.partial(_two_step_block_jnp,
                               codes=codes_j, C=C,
                               fast=fast, sigma=sigma, topk=topk,
                               quantized=quantized, code_bits=code_bits,
                               pred=pred)
    idx, dist, pf = chunked_over_queries(fn, queries, query_chunk)
    pass_rate = jnp.mean(pf)
    avg_ops = kf + pass_rate * (K - kf)
    return SearchResult(idx, dist, avg_ops, pass_rate)


def two_step_search_compact(queries, codes, C, structure, topk: int,
                            refine_cap: int, *,
                            query_chunk: Optional[int] = None):
    """Back-compat wrapper: the survivor compaction is now the
    ``refine_cap`` option of ``two_step_search``'s dispatch."""
    return two_step_search(queries, codes, C, structure, topk,
                           backend="jnp", query_chunk=query_chunk,
                           refine_cap=refine_cap)


def _flat_crude_only_phase(qs, env, *, topk: int, backend: str,
                           block_q: int = 64, block_n: int = 512,
                           interpret=None, quantized: bool = False,
                           code_bits: int = 8, has_filter: bool = False):
    """The degraded pipeline: a ``CrudeStage`` with the refine stage
    dropped (the resilience ladder's crude rung).  jnp ranks the dense
    crude matrix directly; pallas takes the fused kernel's candidate
    list (``want_crude=False`` — no dense matrix at all).  Returns
    (idx, dist, pf=0) like the full phase pair."""
    stage = CrudeStage(backend=backend, topk=topk, block_q=block_q,
                       block_n=block_n, interpret=interpret,
                       quantized=quantized, code_bits=code_bits,
                       want_crude=False)
    luts = build_lut(qs, env["C"])
    with jax.named_scope("crude"):
        if backend == "pallas":
            out = stage(env["codes"], luts, env["fast"])
            return (out.cand_idx, out.cand_vals,
                    jnp.zeros(qs.shape[0], dtype=jnp.float32))
        pred = env["pred"] if has_filter else None
        crude = stage(env["codes"], luts, env["fast"], pred=pred).crude
    with jax.named_scope("merge"):
        neg_c, cand = jax.lax.top_k(-crude, topk)
        if pred is not None:
            cand = mask_filtered_ids(cand, -neg_c)
    return cand, -neg_c, jnp.zeros(qs.shape[0], dtype=jnp.float32)


def _two_step_crude_block_jnp(qs, codes, C, fast, sigma, topk: int,
                              quantized: bool = False, code_bits: int = 8,
                              pred=None):
    """Crude-only ranking over one query block: the exact crude top-k
    the full jnp path bootstraps eq. 2 candidates from, with no
    refinement."""
    env = {"codes": codes, "C": C, "fast": fast, "pred": pred}
    return _flat_crude_only_phase(qs, env, topk=topk, backend="jnp",
                                  quantized=quantized,
                                  code_bits=code_bits,
                                  has_filter=pred is not None)


def _two_step_crude_pallas(qs, codes, C, fast, topk: int, block_q: int,
                           block_n: int, interpret,
                           quantized: bool = False, code_bits: int = 8):
    """Crude-only ranking via the phase-1 kernel: ``batched_crude_topk``
    already emits the crude top-k (its candidate list); skip the dense
    crude matrix and phase 2 entirely."""
    env = {"codes": codes, "C": C, "fast": fast, "pred": None}
    return _flat_crude_only_phase(qs, env, topk=topk, backend="pallas",
                                  block_q=block_q, block_n=block_n,
                                  interpret=interpret,
                                  quantized=quantized,
                                  code_bits=code_bits)


def two_step_crude_search(queries, codes, C, structure, topk: int, *,
                          backend: str = "auto", block_q: int = 64,
                          block_n: int = 512, interpret=None,
                          query_chunk: Optional[int] = None,
                          lut_dtype: str = "f32", code_bits: int = 8,
                          filter=None):
    """The degradation ladder's crude floor (docs/robustness.md): rank
    by the fast-subset crude distance only, skipping eq. 2 and the
    refine pass.  Bitwise-identical to the crude top-k the full path
    computes internally (the eq. 2 bootstrap candidates), on either
    backend.  ``pass_rate`` is 0 (nothing refined); ``avg_ops`` is
    |K_fast| per point.  Under ``code_bits=4`` this rung serves
    directly from the packed nibbles (fast-scan crude pass).
    ``filter`` (jnp only) masks rows pre-top-k like the full path."""
    fast = structure.fast_mask
    kf = jnp.sum(fast.astype(jnp.float32))
    be = resolve_backend(backend)
    quantized = resolve_lut_dtype(lut_dtype) == "int8"
    code_bits = _check_fastscan_geometry(code_bits, C.shape[1])
    pred = _check_filter(filter, codes.shape[0], be)

    if be == "pallas":
        fn = functools.partial(_two_step_crude_pallas, codes=codes, C=C,
                               fast=fast, topk=topk, block_q=block_q,
                               block_n=block_n, interpret=interpret,
                               quantized=quantized, code_bits=code_bits)
    else:
        codes_j = codes if code_bits == 4 else codes.astype(jnp.int32)
        fn = functools.partial(_two_step_crude_block_jnp,
                               codes=codes_j, C=C,
                               fast=fast, sigma=structure.sigma, topk=topk,
                               quantized=quantized, code_bits=code_bits,
                               pred=pred)
    idx, dist, pf = chunked_over_queries(fn, queries, query_chunk)
    return SearchResult(idx, dist, kf, jnp.mean(pf))


def two_step_phase_env(codes, C, structure, *, backend: str,
                       code_bits: int, pred=None):
    """The borrowed-operand environment the flat phase functions close
    over nothing and read everything from: stored codes (packed into
    the kernels, widened once for the jnp byte path — the same
    ``codes_j`` rule as ``two_step_search``), codebooks, the ICQ
    structure's fast mask and margin, and the optional filter
    predicate."""
    codes_j = (codes if (backend == "pallas" or code_bits == 4)
               else codes.astype(jnp.int32))
    return {"codes": codes_j, "C": C, "fast": structure.fast_mask,
            "sigma": structure.sigma, "pred": pred}


def two_step_phase_fns(*, topk: int, backend: str, block_q: int = 64,
                       block_n: int = 512, interpret=None,
                       quantized: bool = False, code_bits: int = 8,
                       refine_cap: Optional[int] = None,
                       crude_only: bool = False,
                       has_filter: bool = False):
    """The flat two-step engine as a ``(crude_fn, refine_fn)`` phase
    pair over ``(qs | carry, env)`` — the contract
    ``index/pipelined.py`` jits and overlaps.  ``crude_only`` drops the
    refine stage (the degraded rung): refine_fn is None and crude_fn
    returns final (idx, dist, pf) tiles directly."""
    common = dict(topk=topk, backend=backend, block_q=block_q,
                  block_n=block_n, interpret=interpret,
                  quantized=quantized, code_bits=code_bits,
                  has_filter=has_filter)
    if crude_only:
        return functools.partial(_flat_crude_only_phase, **common), None
    crude = functools.partial(_flat_crude_phase, **common)
    refine = functools.partial(_flat_refine_phase, refine_cap=refine_cap,
                               **common)
    return crude, refine


def adc_phase_fns(*, topk: int, backend: str, block_q: int = 64,
                  block_n: int = 512, interpret=None,
                  quantized: bool = False, code_bits: int = 8,
                  has_filter: bool = False):
    """One-step ADC as a phase pair: the whole search is its crude
    stage, so the refine slot is always None (the pipelined executor
    still overlaps tile dispatch)."""
    def crude_fn(qs, env):
        ids, vals = _adc_block(qs, env, topk=topk, backend=backend,
                               block_q=block_q, block_n=block_n,
                               interpret=interpret, quantized=quantized,
                               code_bits=code_bits,
                               has_filter=has_filter)
        return ids, vals, jnp.zeros(qs.shape[0], dtype=jnp.float32)
    return crude_fn, None


# -------------------------------------------------------------- indexes ----

def _encode_new_rows(new_vectors, C, codes_dtype, *, icm_iters: int,
                     encode_backend: str, point_chunk: Optional[int],
                     code_bits: int = 8):
    """Shared ``Index.add`` encode step (DESIGN.md §9): run the tiled
    ICM engine over the new embeddings (PQ warm start; for
    orthogonal-support PQ codebooks the interaction terms vanish, so
    ICM reproduces the independent assignment exactly) and pack to the
    stored codes format (``codes_dtype`` for byte codes; nibble rows
    under ``code_bits=4`` — the dtype is uint8 either way, but the
    packed row width differs)."""
    from repro.core import encode as enc

    new = enc.icm_encode(jnp.asarray(new_vectors), C, icm_iters,
                         backend=encode_backend, point_chunk=point_chunk)
    if code_bits == 4:
        return enc.pack_nibbles(new, C.shape[0])
    return new.astype(codes_dtype)

@dataclasses.dataclass(frozen=True)
class FlatADC:
    """One-step exhaustive ADC index (baseline; no pruning).

    ``lut_dtype="int8"`` quantizes the full per-query table (the whole
    one-step ranking becomes approximate, DESIGN.md §8)."""
    codes: jnp.ndarray                  # (n, K) packed
    C: jnp.ndarray                      # (K, m, d)
    topk: int = 50
    backend: str = "auto"
    block_q: int = 64
    block_n: int = 512
    interpret: Optional[bool] = None
    query_chunk: Optional[int] = None
    lut_dtype: str = "f32"
    code_bits: int = 8
    pipeline: str = "off"               # off | tiles | auto (DESIGN.md §13)
    pipeline_tile: Optional[int] = None

    @classmethod
    def build(cls, codes, C, structure=None, **opts) -> "FlatADC":
        return cls(codes=codes, C=C, **opts)

    def search(self, queries, topk: Optional[int] = None, *,
               filter=None) -> SearchResult:
        k = topk if topk is not None else self.topk
        if self.pipeline != "off":
            from repro.index.pipelined import maybe_pipelined
            res = maybe_pipelined(self, queries, k, filter=filter)
            if res is not None:
                return res
        return adc_search(queries, self.codes, self.C, k,
                          backend=self.backend, block_q=self.block_q,
                          block_n=self.block_n, interpret=self.interpret,
                          query_chunk=self.query_chunk,
                          lut_dtype=self.lut_dtype,
                          code_bits=self.code_bits, filter=filter)

    def search_crude(self, queries, topk: Optional[int] = None, *,
                     filter=None) -> SearchResult:
        """One-step ADC has no cheap/refine split — the crude floor of
        the degradation ladder is the full search itself."""
        return self.search(queries, topk, filter=filter)

    def add(self, new_vectors, *, icm_iters: int = 3,
            encode_backend: str = "auto",
            point_chunk: Optional[int] = 8192) -> "FlatADC":
        """Encode ``new_vectors`` ((n_new, d) embeddings) through the
        tiled engine and append their rows — incremental build, no
        retraining (DESIGN.md §9).  Returns a new index; new rows get
        ids [n, n + n_new)."""
        new = _encode_new_rows(new_vectors, self.C, self.codes.dtype,
                               icm_iters=icm_iters,
                               encode_backend=encode_backend,
                               point_chunk=point_chunk,
                               code_bits=self.code_bits)
        return dataclasses.replace(
            self, codes=jnp.concatenate([self.codes, new], axis=0))

    def shard(self, mesh):
        from repro.index.sharded import ShardedFlatADC
        return ShardedFlatADC(self, mesh)


@dataclasses.dataclass(frozen=True)
class TwoStep:
    """Exhaustive ICQ two-step index (eq. 2 pruning, optional
    ``refine_cap`` compaction, optional int8 crude tables)."""
    codes: jnp.ndarray                  # (n, K) packed
    C: jnp.ndarray                      # (K, m, d)
    structure: object                   # core.icq.ICQStructure
    topk: int = 50
    backend: str = "auto"
    block_q: int = 64
    block_n: int = 512
    interpret: Optional[bool] = None
    query_chunk: Optional[int] = None
    refine_cap: Optional[int] = None
    lut_dtype: str = "f32"
    code_bits: int = 8
    pipeline: str = "off"               # off | tiles | auto (DESIGN.md §13)
    pipeline_tile: Optional[int] = None

    @classmethod
    def build(cls, codes, C, structure, **opts) -> "TwoStep":
        return cls(codes=codes, C=C, structure=structure, **opts)

    def search(self, queries, topk: Optional[int] = None, *,
               filter=None) -> SearchResult:
        k = topk if topk is not None else self.topk
        if self.pipeline != "off":
            from repro.index.pipelined import maybe_pipelined
            res = maybe_pipelined(self, queries, k, filter=filter)
            if res is not None:
                return res
        return two_step_search(queries, self.codes, self.C, self.structure,
                               k,
                               backend=self.backend, block_q=self.block_q,
                               block_n=self.block_n, interpret=self.interpret,
                               query_chunk=self.query_chunk,
                               refine_cap=self.refine_cap,
                               lut_dtype=self.lut_dtype,
                               code_bits=self.code_bits, filter=filter)

    def search_crude(self, queries, topk: Optional[int] = None, *,
                     filter=None) -> SearchResult:
        """Crude-only floor (docs/robustness.md): the fast-subset crude
        ranking, bitwise-identical to the full path's internal eq. 2
        bootstrap candidates on the same backend.  Under an active
        pipeline this is the degraded pipeline — the refine stage is
        dropped and crude tiles stream straight out."""
        k = topk if topk is not None else self.topk
        if self.pipeline != "off":
            from repro.index.pipelined import maybe_pipelined
            res = maybe_pipelined(self, queries, k, filter=filter,
                                  crude_only=True)
            if res is not None:
                return res
        return two_step_crude_search(
            queries, self.codes, self.C, self.structure, k,
            backend=self.backend, block_q=self.block_q,
            block_n=self.block_n, interpret=self.interpret,
            query_chunk=self.query_chunk, lut_dtype=self.lut_dtype,
            code_bits=self.code_bits, filter=filter)

    def add(self, new_vectors, *, icm_iters: int = 3,
            encode_backend: str = "auto",
            point_chunk: Optional[int] = 8192) -> "TwoStep":
        """Encode ``new_vectors`` ((n_new, d) embeddings) through the
        tiled engine and append their rows — incremental build, no
        retraining (DESIGN.md §9).  Returns a new index; new rows get
        ids [n, n + n_new)."""
        new = _encode_new_rows(new_vectors, self.C, self.codes.dtype,
                               icm_iters=icm_iters,
                               encode_backend=encode_backend,
                               point_chunk=point_chunk,
                               code_bits=self.code_bits)
        return dataclasses.replace(
            self, codes=jnp.concatenate([self.codes, new], axis=0))

    def shard(self, mesh):
        from repro.index.sharded import ShardedTwoStep
        return ShardedTwoStep(self, mesh)
