"""Mesh-sharded ANN serving (DESIGN.md §7): packed codes / IVF lists
sharded over the ``data`` mesh axis via ``shard_map``, per-shard local
top-k, and a global merge that returns *bitwise-identical ids* to the
single-device engines (distances agree to float-reassociation ulps:
the SPMD-partitioned program may reassociate the LUT einsum).

Merge discipline: every local top-k carries (distance, global key)
pairs; shards ``all_gather`` their candidate lists and a two-key
ascending ``lax.sort`` on (distance, key) reproduces ``jax.lax.top_k``'s
lowest-index-wins tie-breaking globally.  Because each shard computes
its columns with the same per-column arithmetic as the single-device
engine (LUT sums reduce over K only), the merged ranking — including
the +inf tail and the eq. 2 threshold bootstrap, which is merged
*before* thresholding so every shard prunes against the global
threshold — reproduces the single-device ranking exactly.

Index state (codebooks, centroids, the fast mask and sigma, the
alive-shard mask) enters every ``shard_map`` body as an operand with a
replicated spec, never as a closure: a region may not close over arrays
placed on a mesh, and passing them makes the wrappers work on any mesh a
caller builds (``jax.make_mesh``'s Explicit axes included).  The one
exception is the fused-kernel body's fast mask, a host constant as in
the one-chip programs, which each kernel's codebook narrowing reads.

Backends: ``ShardedTwoStep`` follows the source index's resolved
``backend`` (its ``backend`` attribute).  On "pallas" each shard runs
the one-chip engine's fused crude and refine kernels
(``kernels/stages.py``) over its own rows, with the source's
``block_q`` / ``block_n`` / ``interpret``; the merge above is
unchanged, and ids stay bitwise-identical to the single-device Pallas
engine.  A filtered search keeps the jnp body (the kernels cannot mask
rows by predicate).  ``ShardedFlatADC`` and ``ShardedIVFTwoStep`` run
jnp bodies only and say so (``backend = "jnp"``).  Both cross-chip
merges of every body sit under ``jax.named_scope("shard_merge")``.
Likewise ``pipeline`` (DESIGN.md §13): the sharded clones always serve
``pipeline="off"`` — the shard_map body is one fused SPMD program per
batch, so there is no host-level crude/refine boundary to overlap;
sharding a pipelined index yields a working non-pipelined clone.

``lut_dtype`` *is* honored: with "int8" each shard runs its crude pass
on the quantized tables (DESIGN.md §8).  Calibration is query-global by
construction — ``quantize_lut`` derives scale/bias from the per-query
LUT alone, which is computed from the *replicated* codebooks inside the
shard_map body, so every shard quantizes with the identical affine and
dequantized crude distances merge comparably across shards (a per-shard
min/max would break the global top-k ordering).  The refine/full pass
stays f32 on every shard, and the eq. 2 bootstrap mirrors the
single-device quantized decomposition (quantized-crude + exact-slow),
so sharded ids remain bitwise-identical to the single-device
``lut_dtype="int8"`` engines.

Layouts:
  ShardedFlatADC / ShardedTwoStep   codes rows sharded: shard s owns
      global rows [s*ns, (s+1)*ns); local top-k keys are global row ids.
  ShardedIVFTwoStep                 inverted lists sharded: shard s owns
      list rows [s*Ls, (s+1)*Ls) plus the per-list packed codes slab
      gathered at shard time (codes live *inside* the inverted lists,
      the classic IVF serving layout).  Probes are computed from the
      replicated centroids; a probe slot is processed by exactly the
      shard owning that list, every other shard masks it to
      (+inf, id_max) so no slab position is ever contributed twice.
      Keys are slab positions (probe-slot major) — the single-device
      candidate order.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.encode import unpack_nibbles
from repro.distributed.sharding import shard_map_compat
from repro.index import ivf as ivf_mod
from repro.index.base import (SearchResult, as_filter, build_lut,
                              lut_sum, mask_filtered_ids, quantize_lut,
                              resolve_backend, resolve_code_bits,
                              resolve_lut_dtype)

_I32_MAX = jnp.iinfo(jnp.int32).max


def _put(mesh, x, spec):
    return jax.device_put(x, NamedSharding(mesh, spec))


def _pad_rows(x, rows, fill=0):
    if x.shape[0] == rows:
        return x
    pad = [(0, rows - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad, constant_values=fill)


def _sharded_add(self, new_vectors, **kw):
    """Sharded serving clones are immutable: they copy only the padded,
    sharded arrays (never retaining the source index), so there is
    nothing to append to.  Grow the *source* index with ``Index.add``
    and re-shard — ``quant.serve_icq.build_ann_engine`` keeps the
    source index and does exactly that in its ``add``."""
    raise NotImplementedError(
        "sharded indexes are serving clones: call add() on the source "
        "index and re-shard(mesh) (or use build_ann_engine(...).add, "
        "which keeps the source index for you)")


def _shard_row_filter(self, filter):
    """Validate an (n,) row predicate and lay it out P("data") alongside
    the row-sharded codes (pad rows fill False — a pad row is never a
    real candidate anyway)."""
    f = as_filter(filter, self.n)
    D = _data_size(self.mesh)
    return _put(self.mesh, _pad_rows(f, D * self.ns, fill=False),
                P("data"))


def _gather_sorted(cols, axis_name: str, num_keys: int = 2):
    """all_gather each (nq, k) operand along the shard axis and two-key
    sort ascending — the global merge primitive.  Returns the sorted
    (nq, D*k) operands."""
    gathered = tuple(jax.lax.all_gather(c, axis_name, axis=1, tiled=True)
                     for c in cols)
    return jax.lax.sort(gathered, dimension=1, num_keys=num_keys)


def _data_size(mesh) -> int:
    return mesh.shape["data"]


def _program(mesh, body, n_state: int, in_specs, out_specs):
    """Jit ``body(qs, *state, *arrays)`` under ``shard_map``: the query
    batch and ``n_state`` state operands replicated, ``arrays`` laid out
    by ``in_specs``."""
    return jax.jit(shard_map_compat(
        body, mesh, in_specs=(P(),) * (1 + n_state) + in_specs,
        out_specs=out_specs))


def _bind(mesh, fn, state):
    """``fn`` with the replicated ``state`` operands bound: the returned
    callable takes ``(qs, *arrays)`` like the search entry points."""
    state = tuple(_put(mesh, x, P()) for x in state)
    return lambda qs, *arrays: fn(qs, *state, *arrays)


def _sanitize(dist):
    """Defensive NaN/Inf scrub for per-shard distances: a poisoned
    shard's garbage must sort dead-last, never win a top-k or wedge the
    two-key merge sort (NaN ordering is unspecified).  Bitwise no-op on
    finite data, so the healthy path keeps single-device parity."""
    return jnp.nan_to_num(dist, nan=jnp.inf, posinf=jnp.inf,
                          neginf=jnp.inf)


class _DeadShardMixin:
    """Shard-failover surface shared by the sharded serving clones
    (docs/robustness.md).

    ``mark_shard_dead(s, ...)`` excludes shards from serving: every
    compiled body masks a dead shard's contributions to +inf before the
    global merge, so ``search`` returns the surviving shards' merged
    top-k instead of raising — results are exactly the single-device
    ranking restricted to the surviving shards' rows (flat/two-step row
    sharding; for list-sharded IVF, restricted to the surviving shards'
    inverted lists).  ``coverage`` reports the reachable fraction of
    the database; the serving engine surfaces it on ``ResultMeta`` and
    flags the result degraded.

    The dead set is *static* per compiled function — it joins the jit
    cache key — so failover costs one recompile, not a per-batch
    branch.  Marking is in-place (serving clones hold device buffers;
    callers keep their reference) and monotone; a replacement shard
    means re-sharding the source index."""

    dead_shards: frozenset = frozenset()
    # sharded clones never pipeline (module docstring): the engine
    # wrappers probe this field to decide who owns the jit boundary
    pipeline: str = "off"

    def mark_shard_dead(self, *shards: int):
        D = _data_size(self.mesh)
        for s in shards:
            if not 0 <= s < D:
                raise ValueError(f"shard {s} outside [0, {D})")
        dead = self.dead_shards | set(shards)
        if len(dead) >= D:
            raise ValueError(
                f"cannot mark all {D} shards dead — no data would remain "
                "(re-shard the source index instead)")
        self.dead_shards = frozenset(dead)
        return self

    def _dead_key(self):
        return tuple(sorted(self.dead_shards))

    def _alive_arr(self):
        """(D,) bool, True where the shard still serves."""
        D = _data_size(self.mesh)
        alive = np.ones(D, bool)
        alive[list(self.dead_shards)] = False
        return jnp.asarray(alive)

    @property
    def coverage(self) -> float:
        """Reachable fraction of the database's real rows (1.0 = no
        dead shards)."""
        if not self.dead_shards:
            return 1.0
        return self._alive_rows() / max(self.n, 1)


# ------------------------------------------------------------- flat ADC ----

class ShardedFlatADC(_DeadShardMixin):
    """Row-sharded one-step ADC: local full LUT sums + local top-k,
    merged by (distance, global row id).

    Construction (`FlatADC.shard(mesh)`): codes rows are zero-padded up
    to a multiple of the shard count and laid out P("data") — shard s
    owns global rows [s*ns, (s+1)*ns); pad rows are masked to +inf
    before the local top-k so they never merge.  ``lut_dtype`` follows
    the source index (int8 = quantized full-table sums, query-global
    calibration — see module docstring)."""

    def __init__(self, base, mesh):
        self.mesh = mesh
        self.C = _put(mesh, base.C, P())
        n = base.codes.shape[0]
        D = _data_size(mesh)
        self.n = n
        self.ns = -(-n // D)
        self.topk = base.topk
        self.lut_dtype = resolve_lut_dtype(getattr(base, "lut_dtype", "f32"))
        self.code_bits = resolve_code_bits(getattr(base, "code_bits", 8))
        self.codes = _put(mesh, _pad_rows(base.codes, D * self.ns),
                          P("data"))
        self.dead_shards = frozenset()
        self._fns = {}

    backend = "jnp"                     # the shard body is jnp-only

    def _alive_rows(self) -> int:
        # shard s owns real rows [s*ns, min((s+1)*ns, n))
        return sum(max(0, min((s + 1) * self.ns, self.n) - s * self.ns)
                   for s in range(_data_size(self.mesh))
                   if s not in self.dead_shards)

    def _fn(self, topk: int, has_filter: bool = False):
        key = (topk, self._dead_key(), has_filter)
        if key in self._fns:
            return self._fns[key]
        n, ns = self.n, self.ns
        K = self.C.shape[0]
        k_loc = min(topk, ns)
        quantized = self.lut_dtype == "int8"
        code_bits = self.code_bits

        def body(qs, C, alive, codes_shard, *rest):
            si = jax.lax.axis_index("data")
            off = si * ns
            if code_bits == 4:      # nibble slab: unpack once per shard
                codes_shard = unpack_nibbles(codes_shard, K)
            luts = build_lut(qs, C)
            lut = quantize_lut(luts) if quantized else luts
            dist = lut_sum(lut, codes_shard)               # (nq, ns)
            gids = off + jnp.arange(ns, dtype=jnp.int32)
            keep = (gids[None, :] < n) & alive[si]
            if has_filter:
                keep = keep & rest[0][None, :]
            dist = jnp.where(keep, _sanitize(dist), jnp.inf)
            neg, li = jax.lax.top_k(-dist, k_loc)
            with jax.named_scope("shard_merge"):
                mv, mg = _gather_sorted((-neg, jnp.take(gids, li)), "data")
            return mg[:, :topk], mv[:, :topk]

        specs = (P("data"),) + ((P("data"),) if has_filter else ())
        state = (self.C, self._alive_arr())
        fn = _bind(self.mesh, _program(self.mesh, body, len(state), specs,
                                       (P(), P())), state)
        self._fns[key] = fn
        return fn

    def search(self, queries, topk: Optional[int] = None, *,
               filter=None) -> SearchResult:
        """queries (nq, d) f32 -> SearchResult; ids bitwise-identical
        to the single-device engine, distances to reassociation ulps.
        ``filter``: optional (n,) boolean row predicate."""
        topk = self.topk if topk is None else topk
        if filter is not None:
            pred = _shard_row_filter(self, filter)
            idx, dist = self._fn(topk, True)(queries, self.codes, pred)
            idx = mask_filtered_ids(idx, dist)
        else:
            idx, dist = self._fn(topk)(queries, self.codes)
        K = self.C.shape[0]
        return SearchResult(idx, dist, jnp.asarray(float(K)),
                            jnp.asarray(1.0))

    add = _sharded_add

    def shard(self, mesh):
        raise ValueError("index is already sharded")


# ------------------------------------------------------------- two-step ----

def _two_step_jnp_body(*, n, ns, topk, K, quantized, code_bits,
                       has_filter):
    """The jnp shard body: dense per-shard LUT sums over (nq, ns).
    Operands ``(qs, C, fast, sigma, alive, codes_shard[, pred])``."""
    k_loc = min(topk, ns)

    def body(qs, C, fast, sigma, alive, codes_shard, *rest):
        si = jax.lax.axis_index("data")
        off = si * ns
        if code_bits == 4:      # nibble slab: unpack once per shard
            codes_shard = unpack_nibbles(codes_shard, K)
        luts = build_lut(qs, C)
        crude_lut = quantize_lut(luts, fast) if quantized else luts
        crude = lut_sum(crude_lut, codes_shard, fast)      # (nq, ns)
        gids = off + jnp.arange(ns, dtype=jnp.int32)
        keep = (gids[None, :] < n) & alive[si]
        if has_filter:
            # filtered rows: crude +inf, so they can't bootstrap the
            # eq. 2 threshold, can't pass it, and rank dead last —
            # same exclusion semantics as the single-device engine
            keep = keep & rest[0][None, :]
        crude = jnp.where(keep, _sanitize(crude), jnp.inf)

        # phase 1: local crude top-k + local full distances, merged
        # globally before the threshold bootstrap (quantized mode
        # mirrors the single-device decomposition: quantized crude
        # + exact slow)
        neg_c, li = jax.lax.top_k(-crude, k_loc)
        cand_codes = jnp.take(codes_shard, li, axis=0)
        if quantized:
            full_cand = -neg_c + lut_sum(luts, cand_codes, ~fast)
        else:
            full_cand = lut_sum(luts, cand_codes)          # (nq, k_loc)
        with jax.named_scope("shard_merge"):
            sv, _, sf = _gather_sorted(
                (-neg_c, jnp.take(gids, li), full_cand), "data")
        thr = _merged_threshold(sv[:, :topk], sf[:, :topk], sigma)

        # phase 2: prune against the global threshold, local refine
        # top-k, merge by (full distance, global id)
        passed = crude < thr[:, None]
        slow = lut_sum(luts, codes_shard, ~fast)
        ranked = jnp.where(passed, crude + slow, jnp.inf)
        neg, li2 = jax.lax.top_k(-ranked, k_loc)
        with jax.named_scope("shard_merge"):
            mv, mg = _gather_sorted((-neg, jnp.take(gids, li2)), "data")
            pf = jax.lax.psum(
                jnp.sum(passed.astype(jnp.float32), axis=1), "data") / n
        return mg[:, :topk], mv[:, :topk], pf

    return body


def _two_step_pallas_body(*, n, ns, D, topk, fast, quantized, code_bits,
                          block_q, block_n, interpret):
    """The fused-kernel shard body: each shard runs the one-chip
    programs' crude and refine kernels (``kernels/stages.py``) on its
    ``ns`` rows.  Operands as the jnp body's, ``(qs, C, fast_op, sigma,
    alive, codes_shard)``.

    The kernels read ``fast``, the host fast mask, a constant of the
    program as in the one-chip engine, so each contracts over its
    pass's codebooks only; ``fast_op``, the same mask as an operand, is
    unread.  Kernel ids are shard-local; the shard's row offset is added
    here, outside the kernels.  Rows past ``n`` (the last shards' pad to
    ``D * ns``) and every row of a dead shard rank (+inf, id_max): the
    crude kernel returns ``D * ns - n`` extra candidates so that pad
    rows cannot push a real one out of a shard's top-k, a dead shard
    prunes against -inf, and neither reaches the threshold bootstrap."""
    from repro.kernels.stages import CrudeStage, RefineStage, ThresholdStage

    fast = jnp.asarray(fast)
    n_pad = D * ns - n
    common = dict(block_q=block_q, block_n=block_n, interpret=interpret,
                  code_bits=code_bits)
    crude_st = CrudeStage(backend="pallas", topk=topk + n_pad,
                          quantized=quantized, **common)
    thr_st = ThresholdStage(topk=topk, quantized=quantized,
                            code_bits=code_bits)
    refine_st = RefineStage(backend="pallas", topk=topk, **common)

    def global_ids(local, off, rows):
        real = (local < rows) & (local >= 0)
        return real, jnp.where(real, off + local, _I32_MAX)

    def body(qs, C, fast_op, sigma, alive, codes_shard):
        si = jax.lax.axis_index("data")
        off = si * ns
        rows = jnp.where(alive[si], jnp.clip(n - off, 0, ns), 0)
        luts = build_lut(qs, C)
        with jax.named_scope("crude"):
            out = crude_st(codes_shard, luts, fast)

        # phase 1: the shard's crude top-k with full distances, merged
        # across shards before the eq. 2 bootstrap
        with jax.named_scope("threshold"):
            real, gid = global_ids(out.cand_idx, off, rows)
            full = thr_st.candidate_full(
                luts, codes_shard, out.cand_vals,
                jnp.where(real, out.cand_idx, 0), fast)
            vals = jnp.where(real, _sanitize(out.cand_vals), jnp.inf)
        with jax.named_scope("shard_merge"):
            sv, _, sf = _gather_sorted((vals, gid, full), "data")
        with jax.named_scope("threshold"):
            thr = _merged_threshold(sv[:, :topk], sf[:, :topk], sigma)
            thr = jnp.where(alive[si], thr, -jnp.inf)

        # phase 2: the refine kernel against the global threshold, its
        # local top-k merged by (full distance, global id)
        crude = out.crude
        if n_pad:
            crude = jnp.where(
                jnp.arange(ns, dtype=jnp.int32)[None, :] < rows, crude,
                jnp.inf)
        with jax.named_scope("refine"):
            li, dist, passed = refine_st(codes_shard, luts, crude, thr,
                                         fast)
            real, gid = global_ids(li, off, rows)
            dist = jnp.where(real, _sanitize(dist), jnp.inf)
        with jax.named_scope("shard_merge"):
            mv, mg = _gather_sorted((dist, gid), "data")
            pf = jax.lax.psum(
                jnp.sum(passed.astype(jnp.float32), axis=1), "data") / n
        return mg[:, :topk], mv[:, :topk], pf

    return body


def _merged_threshold(sv, sf, sigma):
    """eq. 2's ``t + sigma`` from the merged crude top-k ``sv`` (sorted
    by (crude, id)) and its full distances ``sf``.  +inf crude slots
    (dead shards, tiny dbs) carry garbage full distances and are kept
    out of the far-element argmax (a no-op when the merged top-k is
    fully populated)."""
    far = jnp.argmax(jnp.where(jnp.isfinite(sv), sf, -jnp.inf), axis=1)
    t = jnp.take_along_axis(sv, far[:, None], axis=1)[:, 0]
    return t + sigma


def two_step_program(mesh, *, n: int, topk: int, K: int, fast,
                     backend: str, quantized: bool = False,
                     code_bits: int = 8, has_filter: bool = False,
                     block_q: int = 64, block_n: int = 512,
                     interpret=None):
    """The jitted sharded two-step search over ``mesh``'s "data" axis,
    with its state unbound: ``fn(qs, C, fast, sigma, alive, codes[,
    pred]) -> (ids, dist, passed_frac (nq,))``, ``passed_frac`` counted
    over the ``n`` real rows.  The body is the fused kernels' on
    ``backend="pallas"`` without a filter and the jnp one otherwise;
    both take the same operands.  ``fast`` is the host fast mask.
    ``ShardedTwoStep`` binds the state; a compile test lowers the
    program from shapes alone."""
    D = _data_size(mesh)
    ns = -(-n // D)
    if backend == "pallas" and not has_filter:
        body = _two_step_pallas_body(
            n=n, ns=ns, D=D, topk=topk, fast=np.asarray(fast, bool),
            quantized=quantized, code_bits=code_bits, block_q=block_q,
            block_n=block_n, interpret=interpret)
    else:
        body = _two_step_jnp_body(n=n, ns=ns, topk=topk, K=K,
                                  quantized=quantized, code_bits=code_bits,
                                  has_filter=has_filter)
    specs = (P("data"),) + ((P("data"),) if has_filter else ())
    return _program(mesh, body, 4, specs, (P(), P(), P()))


class ShardedTwoStep(_DeadShardMixin):
    """Row-sharded ICQ two-step.  The eq. 2 threshold is bootstrapped
    from the *merged* global crude top-k (each shard refines its local
    crude candidates, shards exchange (crude, gid, full) triples), so
    every shard prunes against the exact single-device threshold.

    Construction (`TwoStep.shard(mesh)`): codes rows zero-padded to a
    multiple of the shard count, laid out P("data"); pad rows rank last
    in every local top-k.  ``backend`` is the source index's, resolved:
    "pallas" runs the fused crude and refine kernels on each shard (a
    filtered search keeps the jnp body), "jnp" the dense jnp body.
    ``lut_dtype="int8"`` quantizes the crude pass per shard with the
    query-global affine (module docstring); the slow/full tables stay
    f32."""

    def __init__(self, base, mesh):
        self.mesh = mesh
        self.C = _put(mesh, base.C, P())
        self.structure = base.structure
        n = base.codes.shape[0]
        D = _data_size(mesh)
        self.n = n
        self.ns = -(-n // D)
        self.topk = base.topk
        self.backend = resolve_backend(getattr(base, "backend", "auto"))
        self.block_q = getattr(base, "block_q", 64)
        self.block_n = getattr(base, "block_n", 512)
        self.interpret = getattr(base, "interpret", None)
        self.lut_dtype = resolve_lut_dtype(getattr(base, "lut_dtype", "f32"))
        self.code_bits = resolve_code_bits(getattr(base, "code_bits", 8))
        self.codes = _put(mesh, _pad_rows(base.codes, D * self.ns),
                          P("data"))
        self.dead_shards = frozenset()
        self._fns = {}

    def _alive_rows(self) -> int:
        return sum(max(0, min((s + 1) * self.ns, self.n) - s * self.ns)
                   for s in range(_data_size(self.mesh))
                   if s not in self.dead_shards)

    def _fn(self, topk: int, has_filter: bool = False):
        key = (topk, self._dead_key(), has_filter)
        if key in self._fns:
            return self._fns[key]
        st = self.structure
        fn = two_step_program(
            self.mesh, n=self.n, topk=topk, K=self.C.shape[0],
            fast=np.asarray(st.fast_mask), backend=self.backend,
            quantized=self.lut_dtype == "int8", code_bits=self.code_bits,
            has_filter=has_filter, block_q=self.block_q,
            block_n=self.block_n, interpret=self.interpret)
        self._fns[key] = _bind(self.mesh, fn, (self.C, st.fast_mask,
                                               st.sigma, self._alive_arr()))
        return self._fns[key]

    def search(self, queries, topk: Optional[int] = None, *,
               filter=None) -> SearchResult:
        """queries (nq, d) f32 -> SearchResult; ids and pass accounting
        bitwise-identical to the single-device engine of the same
        backend.  ``filter``: optional (n,) boolean row predicate (the
        jnp body serves it on either backend)."""
        topk = self.topk if topk is None else topk
        if filter is not None:
            pred = _shard_row_filter(self, filter)
            idx, dist, pf = self._fn(topk, True)(queries, self.codes,
                                                 pred)
            idx = mask_filtered_ids(idx, dist)
        else:
            idx, dist, pf = self._fn(topk)(queries, self.codes)
        K = self.C.shape[0]
        kf = jnp.sum(self.structure.fast_mask.astype(jnp.float32))
        pass_rate = jnp.mean(pf)
        return SearchResult(idx, dist, kf + pass_rate * (K - kf), pass_rate)

    add = _sharded_add

    def shard(self, mesh):
        raise ValueError("index is already sharded")


# ------------------------------------------------------------------ IVF ----

class ShardedIVFTwoStep(_DeadShardMixin):
    """List-sharded batched IVF: shard s owns list rows
    [s*Ls, (s+1)*Ls) and their packed codes slab.  Candidate keys are
    slab positions (probe-slot major), identical to the single-device
    candidate order, so the merged ranking is bitwise-equal.

    Construction (`IVFTwoStep.shard(mesh)`): list rows and the in-list
    codes slab are padded to a multiple of the shard count (pad lists
    all-invalid, id -1) and laid out P("data"); centroids/codebooks are
    replicated.  ``lut_dtype="int8"`` runs each shard's slab crude pass
    on the query-global quantized tables (module docstring); the
    refine/full pass stays f32."""

    def __init__(self, base, mesh):
        # copy fields rather than retaining base: the sharded clone must
        # not pin the replicated codes/slab arrays for its lifetime
        self.mesh = mesh
        self.C = _put(mesh, base.C, P())
        self.structure = base.structure
        self.centroids = _put(mesh, base.ivf.centroids, P())
        n_lists, max_len = base.ivf.lists.shape
        D = _data_size(mesh)
        self.n = base.codes.shape[0]
        self.n_lists = n_lists
        self.max_len = max_len
        self.Ls = -(-n_lists // D)
        self.n_probe = base.n_probe
        self.topk = base.topk
        self.refine_cap = base.refine_cap
        self.lut_dtype = resolve_lut_dtype(getattr(base, "lut_dtype", "f32"))
        self.code_bits = resolve_code_bits(getattr(base, "code_bits", 8))
        lists_p = _pad_rows(base.ivf.lists, D * self.Ls, fill=-1)
        # codes live inside the inverted lists (ivf_list_codes slab) so
        # serving never touches the flat codes array; pad rows are
        # all-invalid (validity rides on the id slab)
        slab = (base.list_codes if base.list_codes is not None
                else ivf_mod.ivf_list_codes(base.ivf, base.codes))
        slab = _pad_rows(slab, D * self.Ls)
        self.lists = _put(mesh, lists_p, P("data"))
        self.list_codes = _put(mesh, slab, P("data"))
        # host-side per-list sizes (padded rows own 0 points) so
        # ``coverage`` under dead shards is computable without a gather
        lens = np.zeros(D * self.Ls, np.int64)
        lens[:n_lists] = np.asarray(base.ivf.list_lens)
        self._list_lens = lens
        self.dead_shards = frozenset()
        self._fns = {}

    backend = "jnp"                     # the shard body is jnp-only

    def _alive_rows(self) -> int:
        # shard s owns list rows [s*Ls, (s+1)*Ls); its reachable points
        # are the sizes of those inverted lists
        Ls = self.Ls
        return int(sum(self._list_lens[s * Ls:(s + 1) * Ls].sum()
                       for s in range(_data_size(self.mesh))
                       if s not in self.dead_shards))

    def _fn(self, topk: int, has_filter: bool = False):
        key = (topk, self._dead_key(), has_filter)
        if key in self._fns:
            return self._fns[key]
        n_probe, Ls, max_len = self.n_probe, self.Ls, self.max_len
        refine_cap = self.refine_cap
        # a shard owns at most min(n_probe, Ls) of a query's probes:
        # compact the owned probe slots into that static bound so the
        # per-shard slab sweep is ~1/D of the single-device work (the
        # point of partition-parallel serving), instead of scoring the
        # full n_probe slab with non-owned columns masked
        P_loc = min(n_probe, Ls)
        nc0 = n_probe * max_len                  # single-device slab width
        nc = max(nc0, topk)
        nc_loc0 = P_loc * max_len
        nc_loc = max(nc_loc0, topk)
        k_loc = min(topk, nc_loc)
        cap = (None if refine_cap is None
               else min(max(refine_cap, topk), nc))
        cap_loc = None if cap is None else min(cap, nc_loc)
        quantized = self.lut_dtype == "int8"
        code_bits = self.code_bits

        def body(qs, C, centroids, fast, sigma, alive, lists_sh, slab_sh,
                 *rest):
            si = jax.lax.axis_index("data")
            L0 = si * Ls
            nq = qs.shape[0]
            luts = build_lut(qs, C)
            probes = ivf_mod.coarse_probe(qs, centroids, n_probe)
            local = (probes >= L0) & (probes < L0 + Ls)    # (nq, n_probe)
            # owned probe slots first, in slot order (rank = slot index
            # for owned, n_probe for the rest; top_k of the negation)
            slot = jnp.arange(n_probe, dtype=jnp.int32)[None, :]
            _, sel = jax.lax.top_k(-jnp.where(local, slot, n_probe), P_loc)
            sel_local = jnp.take_along_axis(local, sel, axis=1)
            rows = jnp.where(
                sel_local, jnp.take_along_axis(probes, sel, axis=1) - L0, 0)
            ids = jnp.where(sel_local[:, :, None], lists_sh[rows], -1)
            ids = ids.reshape(nq, nc_loc0)
            codes = slab_sh[rows].reshape(nq, nc_loc0, -1)  # packed dtype
            if code_bits == 4:  # nibble slab: unpack the gathered rows
                codes = unpack_nibbles(codes, C.shape[0])
            owned = jnp.repeat(sel_local, max_len, axis=1)  # (nq, nc_loc0)
            # global slab positions (probe-slot major — the
            # single-device candidate order) of the compacted columns
            pos = (sel[:, :, None] * max_len
                   + jnp.arange(max_len, dtype=jnp.int32)[None, None, :]
                   ).reshape(nq, nc_loc0)
            if nc_loc > nc_loc0:                 # tiny-slab pad columns
                extra = nc_loc - nc_loc0         # (global pos nc0..nc-1,
                ids = jnp.pad(ids, ((0, 0), (0, extra)),  # shard 0 owns)
                              constant_values=-1)
                codes = jnp.pad(codes, ((0, 0), (0, extra), (0, 0)))
                owned = jnp.concatenate(
                    [owned, jnp.broadcast_to(si == 0, (nq, extra))], axis=1)
                pos = jnp.concatenate(
                    [pos, jnp.broadcast_to(
                        nc0 + jnp.arange(extra, dtype=jnp.int32)[None],
                        (nq, extra))], axis=1)
            valid = owned & (ids >= 0) & alive[si]
            safe = jnp.where(valid, ids, 0)
            if has_filter:
                # replicated (n,) predicate — same exclusion as the
                # single-device engine's valid &= pred[safe]
                valid = valid & rest[0][safe]

            crude_lut = quantize_lut(luts, fast) if quantized else luts
            crude = lut_sum(crude_lut, codes, fast)        # (nq, nc_loc)
            crude = jnp.where(valid, _sanitize(crude), jnp.inf)
            # a slab position is contributed by its owning shard only;
            # everywhere else it sorts dead last
            pos_key = jnp.where(owned, pos, _I32_MAX)
            cols = jnp.broadcast_to(
                jnp.arange(nc_loc, dtype=jnp.int32)[None], crude.shape)

            # phase 1: local (crude, pos) top-k via two-key sort; full
            # distances only for the k_loc bootstrap candidates; global
            # merge, then the eq. 2 threshold on the merged candidates
            c_s, p_s, col_s = jax.lax.sort((crude, pos_key, cols),
                                           dimension=1, num_keys=2)
            c_s, p_s, col_s = c_s[:, :k_loc], p_s[:, :k_loc], col_s[:, :k_loc]
            cand_codes = jnp.take_along_axis(codes, col_s[:, :, None],
                                             axis=1)
            if quantized:       # quantized crude + exact slow (§8)
                full_cand = c_s + lut_sum(luts, cand_codes, ~fast)
            else:
                full_cand = lut_sum(luts, cand_codes)      # (nq, k_loc)
            with jax.named_scope("shard_merge"):
                sv, sp, sf = _gather_sorted((c_s, p_s, full_cand), "data")
            sv, sf = sv[:, :topk], sf[:, :topk]
            far = jnp.argmax(jnp.where(jnp.isfinite(sv), sf, -jnp.inf),
                             axis=1)
            t = jnp.take_along_axis(sv, far[:, None], axis=1)[:, 0]
            thr = t + sigma
            passed = crude < thr[:, None]

            if cap is None:
                slow = lut_sum(luts, codes, ~fast)
                ranked = jnp.where(passed, crude + slow, jnp.inf)
                r_s, k_s, i_s = jax.lax.sort((ranked, pos_key, safe),
                                             dimension=1, num_keys=2)
                with jax.named_scope("shard_merge"):
                    mv, _, mi = _gather_sorted(
                        (r_s[:, :k_loc], k_s[:, :k_loc], i_s[:, :k_loc]),
                        "data")
                dist, idx = mv[:, :topk], mi[:, :topk]
            else:
                # static compaction: merge the (crude, pos)-best cap
                # survivors globally (full distances computed for the
                # local cap_loc survivors only), then rank the compacted
                # set by full distance (compaction-slot tie-break = the
                # single-device top_k order)
                masked = jnp.where(passed, crude, jnp.inf)
                c2, p2, col2 = jax.lax.sort((masked, pos_key, cols),
                                            dimension=1, num_keys=2)
                c2, p2, col2 = (c2[:, :cap_loc], p2[:, :cap_loc],
                                col2[:, :cap_loc])
                surv_codes = jnp.take_along_axis(codes, col2[:, :, None],
                                                 axis=1)
                f2 = lut_sum(luts, surv_codes)             # (nq, cap_loc)
                i2 = jnp.take_along_axis(safe, col2, axis=1)
                with jax.named_scope("shard_merge"):
                    gv, _, gf, gi = _gather_sorted((c2, p2, f2, i2),
                                                   "data")
                gv, gf, gi = gv[:, :cap], gf[:, :cap], gi[:, :cap]
                ranked = jnp.where(jnp.isfinite(gv), gf, jnp.inf)
                neg, cpos = jax.lax.top_k(-ranked, topk)
                dist = -neg
                idx = jnp.take_along_axis(gi, cpos, axis=1)

            with jax.named_scope("shard_merge"):
                n_cand = jax.lax.psum(
                    jnp.sum(valid.astype(jnp.float32), axis=1), "data")
                n_pass = jax.lax.psum(
                    jnp.sum(passed.astype(jnp.float32), axis=1), "data")
            return idx, dist, n_cand, n_pass

        specs = (P("data"), P("data")) + ((P(),) if has_filter else ())
        st = self.structure
        state = (self.C, self.centroids, st.fast_mask, st.sigma,
                 self._alive_arr())
        fn = _bind(self.mesh, _program(self.mesh, body, len(state), specs,
                                       (P(), P(), P(), P())), state)
        self._fns[key] = fn
        return fn

    def search(self, queries, topk: Optional[int] = None, *,
               filter=None) -> SearchResult:
        """queries (nq, d) f32 -> SearchResult with the generalized IVF
        ops accounting; ids and counts bitwise-identical to the
        single-device engine.  ``filter``: optional (n,) boolean row
        predicate (replicated — list-sharded ids are global)."""
        topk = self.topk if topk is None else topk
        if filter is not None:
            pred = _put(self.mesh, as_filter(filter, self.n), P())
            ids, dist, n_cand, n_pass = self._fn(topk, True)(
                queries, self.lists, self.list_codes, pred)
            ids = mask_filtered_ids(ids, dist)
        else:
            ids, dist, n_cand, n_pass = self._fn(topk)(
                queries, self.lists, self.list_codes)
        K = self.C.shape[0]
        kf = jnp.sum(self.structure.fast_mask.astype(jnp.float32))
        return ivf_mod.ivf_ops_result(ids, dist, n_cand, n_pass, n=self.n,
                                      n_lists=self.n_lists, K=K, kf=kf)

    add = _sharded_add

    def shard(self, mesh):
        raise ValueError("index is already sharded")
