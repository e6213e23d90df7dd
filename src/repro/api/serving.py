"""Config-driven index construction and the ``AnnEngine`` serving
handle — the execution half of ``repro.api`` (docs/api.md).

``build_index`` turns (codes, C, structure) + the config tree's
``IndexConfig``/``ServeConfig`` sections into one of the unified index
layer's implementations; ``AnnEngine`` wraps any index into a jitted,
optionally mesh-sharded, growable query server.  The historical
``quant.serve_icq.build_ann_engine`` kwarg entry survives as a thin
shim over these (its kwargs are folded into a config), so every serving
caller — ``launch/serve.py``, the examples, the benchmarks — now goes
through the same door, and ``load_ann_engine`` opens that door from a
saved artifact directory.

Resilient serving (docs/robustness.md): ``AnnEngine`` is also the
executor of the degradation ladder and the backend failover —

  - ``search(queries, budget=SearchBudget(...))`` picks a ladder level
    (full → capped → probes → crude) per batch from *measured* warm
    wall times against the budget's deadline, and attaches a
    ``ResultMeta`` (level, stages, wall time, coverage, backend) to
    every ``SearchResult``;
  - a Pallas kernel failure blacklists that backend for the engine and
    transparently retries the batch on the jnp engines (bounded
    retries + exponential backoff, ``repro.resilience.retry``);
  - sharded engines survive dead shards (``mark_shard_dead``): the
    surviving shards' merged top-k is returned and ``meta.coverage``
    reports the reachable fraction instead of the call raising.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.api.artifacts import ArtifactError, Artifacts
from repro.api.config import (ConfigError, IndexConfig, ResilienceConfig,
                              ServeConfig)
from repro.obs import span
from repro.resilience.budget import (DEGRADE_LEVELS, ResultMeta,
                                     SearchBudget, validate_budget)
from repro.resilience.retry import BackoffPolicy, retry_with_backoff

# warm-timing EMA weight: recent batches dominate but one outlier
# doesn't whipsaw the ladder choice
_EMA_ALPHA = 0.3


class AnnEngine:
    """A serving handle over one index: callable for query batches and
    growable via ``add`` (DESIGN.md §9).

    ``engine(queries)`` (or ``engine.search(queries)``) runs the jitted
    batched search — the historical ``build_ann_engine`` contract.
    ``engine.add(new_vectors)`` encodes the new embeddings through the
    tiled ICM engine, appends/routes them into the index *without*
    retraining, and refreshes the jitted search (re-sharding over the
    engine's mesh if one was given); the engine keeps the unsharded
    source index precisely so sharded serving stays growable.  Returns
    ``self`` so calls chain.

    Resilience surface (docs/robustness.md):

    ``resilience``      a ``ResilienceConfig`` — default deadline,
                        degraded-rung knobs, failover retry policy.
    ``fault_injector``  a ``repro.resilience.faults.FaultInjector``;
                        when set the engine serves *eagerly* (no outer
                        jit) so the injector's kernel hooks fire per
                        batch, and checks the ``"engine.search"`` stage
                        itself before each batch.
    ``search(..., budget=)``  per-batch ``SearchBudget``; every result
                        carries ``result.meta`` (a ``ResultMeta``).
    ``mark_shard_dead(s, ...)``  (sharded engines) fail shards over:
                        subsequent searches merge the survivors and
                        report ``meta.coverage`` < 1.0.
    ``stats``           served/degraded counters per ladder level and
                        the failover count — the chaos benchmark's
                        degraded-rate source.
    """

    def __init__(self, index, mesh=None, *,
                 resilience: Optional[ResilienceConfig] = None,
                 fault_injector=None, query_tile: Optional[int] = None):
        self.index = index                   # the unsharded source index
        self.mesh = mesh
        self.resilience = resilience or ResilienceConfig()
        self.fault_injector = fault_injector
        # canonical query-batch tile (rows).  None: each arrival shape
        # compiles its own program (historical behavior).  Set: every
        # search runs as ceil(nq/tile) zero-padded (tile, d) chunks of
        # ONE compiled program — so results are bitwise-independent of
        # how rows were batched (XLA reduction order varies with the
        # compiled batch size, and last-ulp distance drift across
        # shapes is real).  The serving loop pins this to its flush
        # tile, which is what makes coalesced responses bitwise-equal
        # to direct calls on the same engine (docs/serving.md).
        self.query_tile = query_tile
        self._blacklist: set = set()         # backends failed over from
        self._ema: Dict[str, float] = {}     # level -> warm wall-ms EMA
        self._warmed: set = set()            # fn cache keys that compiled
        self.stats: Dict[str, int] = {"degraded": 0, "failovers": 0}
        self._refresh()

    # ---------------------------------------------------------- plumbing --
    def _refresh(self):
        if self.mesh is not None:
            src = self.index
            if ("pallas" in self._blacklist
                    and getattr(src, "backend", None) is not None):
                src = dataclasses.replace(src, backend="jnp")
            view = src.shard(self.mesh)
            # a refresh (engine.add) must not resurrect failed shards
            dead = (getattr(self._view, "dead_shards", frozenset())
                    if hasattr(self, "_view") else frozenset())
            if dead:
                view.mark_shard_dead(*dead)
            self._view = view
        else:
            self._view = self.index
        self._fns: Dict[Tuple, Any] = {}
        self._cols: Dict[Tuple, Tuple[int, int]] = {}
        self._warmed = set()

    def _backend_eff(self) -> str:
        """The backend the engine currently dispatches to, after
        failover blacklisting.  Under a mesh it is the one the sharded
        view runs, its ``backend`` attribute: the source's on the
        two-step index, "jnp" on the flat and IVF ones."""
        from repro.index.base import resolve_backend

        if self.mesh is not None:
            be = getattr(self._view, "backend", "jnp")
        else:
            be = resolve_backend(getattr(self.index, "backend", "auto"))
        return "jnp" if be in self._blacklist else be

    def _levels(self) -> Tuple[str, ...]:
        """Ladder rungs this engine can serve, least → most degraded."""
        from repro.index import FlatADC, IVFTwoStep, TwoStep

        if self.mesh is not None:
            return ("full",)                 # sharded: full search only
        idx = self.index
        if isinstance(idx, FlatADC):
            return ("full", "crude")         # crude == full (no refine)
        capped = () if self._backend_eff() == "pallas" else ("capped",)
        if isinstance(idx, IVFTwoStep):
            return ("full",) + capped + ("probes", "crude")
        if isinstance(idx, TwoStep):
            return ("full",) + capped + ("crude",)
        # custom Index implementations: full only (plus crude when they
        # provide the protocol's optional search_crude)
        return (("full", "crude") if hasattr(idx, "search_crude")
                else ("full",))

    def _level_index(self, level: str, budget: SearchBudget):
        """The index variant serving one ladder rung — built from the
        frozen source index via ``dataclasses.replace`` (cheap: array
        fields are shared, only engine options change)."""
        idx = self.index
        repl: Dict[str, Any] = {}
        be = self._backend_eff()
        if getattr(idx, "backend", None) is not None and \
                be != getattr(idx, "backend"):
            repl["backend"] = be
        if level == "capped":
            cap = (budget.refine_cap
                   if budget.refine_cap is not None
                   else self.resilience.degraded_refine_cap)
            repl["refine_cap"] = cap if cap is not None else \
                max(4 * self._topk_default(), 64)
        if hasattr(idx, "n_probe"):
            np_eff = int(idx.n_probe)
            if level == "probes":
                np_eff = max(self.resilience.min_n_probe, np_eff // 2)
            if budget.max_n_probe is not None:
                np_eff = min(np_eff, budget.max_n_probe)
            np_eff = max(1, np_eff)
            if np_eff != int(idx.n_probe):
                repl["n_probe"] = np_eff
        return dataclasses.replace(idx, **repl) if repl else idx

    def _kernel_cols(self, level: str) -> Tuple[int, int]:
        """``(crude_cols, refine_cols)``: the contraction width of the
        fused crude and refine kernels at this rung, 0 where the rung
        runs no such kernel (the jnp engines and jnp shard bodies, the
        refine of the crude rung); a sharded view on the kernels runs
        the source index's widths on every shard.  Read off the LUT
        operands the search stages build from this index's fast mask,
        which the engine's programs hold as a constant, so it is what
        those kernels receive on the backend the span's ``backend``
        names.  Like ``backend``, it is fixed as the call starts: a call
        that fails over to jnp reports the Pallas widths, and the calls
        after it 0."""
        be = self._backend_eff()
        cols = self._cols.get((level, be))
        if cols is None:
            from repro.index.base import resolve_lut_dtype
            from repro.kernels.stages import kernel_columns

            idx = self.index
            C = getattr(idx, "C", None)
            cols = (0, 0)
            if be == "pallas" and C is not None:
                st = getattr(idx, "structure", None)
                crude, refine = kernel_columns(
                    None if st is None else st.fast_mask, *C.shape[:2],
                    quantized=resolve_lut_dtype(
                        getattr(idx, "lut_dtype", "f32")) == "int8",
                    code_bits=getattr(idx, "code_bits", 8))
                cols = (crude, 0 if level == "crude" else refine)
            self._cols[(level, be)] = cols
        return cols

    def _topk_default(self) -> int:
        return int(getattr(self.index, "topk", 50))

    def _level_fn(self, level: str, topk: Optional[int],
                  budget: SearchBudget, has_filter: bool = False):
        lidx = (self._view if self.mesh is not None
                else self._level_index(level, budget))
        key = (level, topk, self._backend_eff(),
               getattr(lidx, "refine_cap", None),
               getattr(lidx, "n_probe", None),
               getattr(self._view, "dead_shards", None), has_filter)
        if key in self._fns:
            return key, self._fns[key]
        crude = level == "crude" and hasattr(lidx, "search_crude")
        if has_filter:
            if crude:
                call = (lambda q, f: lidx.search_crude(q, filter=f)) \
                    if topk is None \
                    else (lambda q, f: lidx.search_crude(q, topk, filter=f))
            else:
                call = (lambda q, f: lidx.search(q, filter=f)) \
                    if topk is None \
                    else (lambda q, f: lidx.search(q, topk, filter=f))
        elif crude:
            call = (lambda q: lidx.search_crude(q)) if topk is None \
                else (lambda q: lidx.search_crude(q, topk))
        else:
            call = (lambda q: lidx.search(q)) if topk is None \
                else (lambda q: lidx.search(q, topk))
        # under a fault injector the engine must stay eager: kernel
        # hooks fire at trace time only inside jit, so a jitted fn
        # would check faults once per compile instead of per batch
        # (sharded views run their own inner jit either way); a
        # pipelined index also stays eager — the executor runs a
        # host-level tile loop and owns its own jit/donation boundary,
        # which an outer trace would unroll and defeat
        if (self.fault_injector is None and self.mesh is None
                and getattr(lidx, "pipeline", "off") == "off"):
            call = jax.jit(call)
        self._fns[key] = call
        return key, call

    # ------------------------------------------------------ level choice --
    def _estimate_ms(self, level: str, order: Tuple[str, ...]):
        """Expected warm wall time for a rung: its own EMA, else the
        best measured less-degraded rung as an upper bound (a more
        degraded rung never runs slower), else None (unknown)."""
        if level in self._ema:
            return self._ema[level]
        upper = [self._ema[l] for l in order[:order.index(level)]
                 if l in self._ema]
        return min(upper) if upper else None

    def _pick_level(self, budget: SearchBudget) -> str:
        order = self._levels()
        if budget.force_level is not None:
            if budget.force_level not in order:
                raise ValueError(
                    f"force_level={budget.force_level!r} is not servable "
                    f"by this engine (available: {list(order)})")
            return budget.force_level
        if not budget.allow_refine:
            return "crude" if "crude" in order else order[-1]
        # hard caps promote their rung outright (deterministic, no
        # timing involved): a refine_cap asks for the capped rung, a
        # max_n_probe below the index's n_probe asks for probes
        floor_i = 0
        if budget.refine_cap is not None and "capped" in order:
            floor_i = max(floor_i, order.index("capped"))
        if (budget.max_n_probe is not None and "probes" in order
                and budget.max_n_probe < int(getattr(self.index,
                                                     "n_probe", 1))):
            floor_i = max(floor_i, order.index("probes"))
        order = order[floor_i:]
        deadline = (budget.deadline_ms if budget.deadline_ms is not None
                    else self.resilience.deadline_ms)
        if deadline is None:
            return order[0]
        # measured choice: least-degraded rung whose estimate fits; a
        # rung with no estimate at all (cold engine) is taken
        # optimistically — the measurement it produces steers the next
        # batch; the crude floor is always eligible
        for name in order:
            est = self._estimate_ms(name, self._levels())
            if est is None or est <= deadline:
                return name
        return order[-1]

    # ------------------------------------------------------------ serving --
    def _stages(self, level: str) -> Tuple[str, ...]:
        from repro.index import FlatADC, IVFTwoStep

        idx = self.index
        probe = ("probe",) if (isinstance(idx, IVFTwoStep)
                               or hasattr(idx, "n_probe")) else ()
        if isinstance(idx, FlatADC):
            return probe + ("adc",)
        if level == "crude":
            return probe + ("crude",)
        if level == "capped":
            return probe + ("crude", "refine-capped")
        return probe + ("crude", "refine")

    def _attempt(self, fn, *args, chunk: int = 0):
        if self.fault_injector is not None:
            self.fault_injector.check("engine.search")
        with span("engine.dispatch", chunk=chunk):
            r = fn(*args)
        with span("engine.wait", chunk=chunk):
            jax.block_until_ready((r.indices, r.distances))
        return r

    def _run_tiled(self, fn, queries, filter=None):
        """Run one rung's fn over the batch.  Without ``query_tile``
        this is a single call at the arrival shape; with it, the batch
        runs as zero-padded (tile, d) chunks of one compiled program
        and the pad rows are sliced off — per-row results are invariant
        to position and neighbors within a fixed compiled shape, so
        chunking never changes any row's answer (tests/test_serve.py
        holds this bitwise)."""
        tile = self.query_tile
        nq = queries.shape[0]
        if tile is None:
            args = (queries,) if filter is None else (queries, filter)
            return self._attempt(fn, *args)
        tile = int(tile)
        parts = []
        for i, s in enumerate(range(0, max(nq, 1), tile)):
            chunk = queries[s:s + tile]
            pad = tile - chunk.shape[0]
            if pad:
                with span("engine.pad", pad=pad):
                    chunk = jnp.concatenate(
                        [chunk, jnp.zeros((pad, chunk.shape[1]),
                                          dtype=chunk.dtype)], axis=0)
            args = (chunk,) if filter is None else (chunk, filter)
            parts.append(self._attempt(fn, *args, chunk=i))
        with span("engine.assemble"):
            if len(parts) == 1:
                r = parts[0]
                ids, dists = r.indices[:nq], r.distances[:nq]
            else:
                r = parts[-1]
                ids = jnp.concatenate([p.indices for p in parts],
                                      axis=0)[:nq]
                dists = jnp.concatenate([p.distances for p in parts],
                                        axis=0)[:nq]
            # avg_ops/pass_rate are padded-batch diagnostics (mean over
            # chunks); the bitwise contract covers ids + distances only
            k = len(parts)
            return r._replace(
                indices=ids, distances=dists,
                avg_ops=sum(p.avg_ops for p in parts) / k,
                pass_rate=sum(p.pass_rate for p in parts) / k)

    def _serve_with_failover(self, level, topk, budget, queries,
                             filter=None):
        """One batch at one rung, with backend failover: a failure on
        the pallas backend blacklists it for the whole engine and the
        batch retries on the jnp engines under the configured backoff;
        jnp/sharded failures retry in place (transient-fault model)."""
        res = self.resilience
        policy = BackoffPolicy(max_retries=res.max_retries,
                               base_ms=res.backoff_base_ms,
                               max_ms=res.backoff_max_ms)
        has_filter = filter is not None
        key, fn = self._level_fn(level, topk, budget, has_filter)
        try:
            return key, self._run_tiled(fn, queries, filter)
        except Exception as e:
            if res.pallas_failover and self._backend_eff() == "pallas":
                # kernel path failed: fail the backend over, not the
                # query — rebuild this rung on jnp and retry bounded.
                # Never silently: the warning names the kernel error and
                # ``stats["failovers"]`` counts it.
                warnings.warn(
                    f"pallas search failed ({type(e).__name__}: {e}); "
                    "failing over to the jnp engines", RuntimeWarning,
                    stacklevel=3)
                self._blacklist.add("pallas")
                self.stats["failovers"] += 1
                if self.mesh is not None:
                    self._refresh()          # re-shard onto the jnp body
                self._fns.clear()
                self._warmed.discard(key)
                key, fn = self._level_fn(level, topk, budget, has_filter)
            return key, retry_with_backoff(
                lambda: self._run_tiled(fn, queries, filter),
                policy=policy)

    def __call__(self, queries, budget: Optional[SearchBudget] = None):
        return self.search(queries, budget=budget)

    def search(self, queries, k: Optional[int] = None, *,
               budget: Optional[SearchBudget] = None, filter=None):
        """Serve one query batch; ``k`` overrides the index's built-in
        ``topk`` for this call.  ``budget`` (docs/robustness.md) bounds
        the batch — the engine picks the degradation-ladder rung that
        fits and reports what it did on ``result.meta``.  ``filter``: an
        optional (n,) boolean row predicate — only rows where it is
        True can be returned; absent slots are id -1 / dist +inf
        (jnp engines only)."""
        if filter is not None:
            from repro.index.base import as_filter
            if self._backend_eff() == "pallas":
                raise ValueError(
                    "filtered search requires backend='jnp' (the fused "
                    "kernels cannot mask rows by predicate)")
            filter = as_filter(filter, self.n)
        budget = validate_budget(budget) if budget is not None \
            else SearchBudget()
        level = self._pick_level(budget)
        deadline = (budget.deadline_ms if budget.deadline_ms is not None
                    else self.resilience.deadline_ms)
        crude_cols, refine_cols = self._kernel_cols(level)
        shards = 1 if self.mesh is None else int(self.mesh.shape["data"])
        # the span covers what ``wall_ms`` times
        with span("engine.search", level=level,
                  backend=self._backend_eff(), rows=len(queries),
                  crude_cols=crude_cols, refine_cols=refine_cols,
                  shards=shards):
            t0 = time.perf_counter()
            key, result = self._serve_with_failover(level, k, budget,
                                                    queries, filter)
            wall_ms = (time.perf_counter() - t0) * 1000.0
        # warm-only timing: the first call through a compiled fn pays
        # tracing + compilation and would poison the ladder's estimates
        if key in self._warmed:
            prev = self._ema.get(level)
            self._ema[level] = wall_ms if prev is None else \
                (1 - _EMA_ALPHA) * prev + _EMA_ALPHA * wall_ms
        else:
            self._warmed.add(key)
        coverage = float(getattr(self._view, "coverage", 1.0))
        li = DEGRADE_LEVELS.index(level)
        meta = ResultMeta(
            level=li, level_name=level,
            degraded=li > 0 or coverage < 1.0,
            stages=self._stages(level), wall_ms=wall_ms,
            deadline_ms=deadline,
            deadline_exceeded=(deadline is not None and wall_ms > deadline),
            coverage=coverage, backend=self._backend_eff())
        self.stats[level] = self.stats.get(level, 0) + 1
        if meta.degraded:
            self.stats["degraded"] += 1
        return result._replace(meta=meta)

    def warm(self, nq: int, k: Optional[int] = None, *,
             budget: Optional[SearchBudget] = None) -> "AnnEngine":
        """Precompile the (nq, d) program one ``search(queries, k,
        budget=...)`` call would run and mark it warm, so the first real
        batch at that shape pays dispatch instead of trace+compile (and
        its timing feeds the ladder's EMA instead of being discarded as
        a cold call).  The serving loop warms its flush-tile shape this
        way (``repro.serve.ServingLoop.warm``); warming an
        already-compiled shape is a cheap no-op (jit's signature cache
        hits)."""
        budget = validate_budget(budget) if budget is not None \
            else SearchBudget()
        level = self._pick_level(budget)
        key, fn = self._level_fn(level, k, budget)
        d = int(self.index.C.shape[-1])
        zeros = jnp.zeros((int(nq), d), dtype=jnp.float32)
        self._run_tiled(fn, zeros)
        self._warmed.add(key)
        return self

    # ------------------------------------------------------------- shards --
    def mark_shard_dead(self, *shards: int) -> "AnnEngine":
        """Fail shards over (sharded engines only): subsequent searches
        merge the surviving shards' top-k and report the reachable
        fraction on ``meta.coverage`` instead of raising."""
        if self.mesh is None:
            raise ValueError("mark_shard_dead needs a sharded engine "
                             "(AnnEngine(mesh=...))")
        self._view.mark_shard_dead(*shards)
        return self

    @property
    def served(self):
        """The index that answers searches: the sharded serving clone
        on a mesh, else the source index."""
        return self._view

    @property
    def coverage(self) -> float:
        return float(getattr(self._view, "coverage", 1.0))

    @property
    def n(self) -> int:
        return self.index.codes.shape[0]

    def add(self, new_vectors, **encode_opts) -> "AnnEngine":
        self.index = self.index.add(new_vectors, **encode_opts)
        self._refresh()
        return self


def build_index(codes, C, structure, *, index_cfg: IndexConfig,
                serve_cfg: ServeConfig, emb_db=None, key=None):
    """Build an index from the config tree's sections — THE construction
    path behind ``ICQSession.index``, ``build_ann_engine``, and artifact
    loading (``api.artifacts._index_opts`` mirrors the option
    resolution, which is what makes a loaded index serve identically).

    ``emb_db`` (the embeddings the codes encode) is required for
    ``index_cfg.kind == "ivf"``; ``key`` seeds its coarse k-means.

    ``index_cfg.code_bits == 4`` stores the database nibble-packed
    (DESIGN.md §12): byte-per-code ``codes`` arriving here (the
    ``encode_database`` output) are packed two-per-byte before
    device_put; codes already in the (n, ceil(K/2)) layout are taken
    as-is, so a loaded artifact round-trips bitwise.
    """
    from repro.core.encode import pack_nibbles
    from repro.index import make_index, resolve_code_bits

    code_bits = resolve_code_bits(index_cfg.code_bits)
    if code_bits == 4:
        if C.shape[1] > 16:
            raise ConfigError(
                f"index.code_bits=4 requires codebook_size <= 16 "
                f"codewords (4-bit codes), got m={C.shape[1]}; set "
                "train.codebook_size <= 16 or keep index.code_bits=8")
        if codes.shape[-1] == C.shape[0] and C.shape[0] > 1:
            codes = pack_nibbles(codes, C.shape[0])

    opts: Dict[str, Any] = dict(topk=serve_cfg.topk,
                                backend=serve_cfg.backend,
                                query_chunk=serve_cfg.query_chunk,
                                lut_dtype=serve_cfg.lut_dtype,
                                code_bits=code_bits,
                                pipeline=serve_cfg.pipeline,
                                pipeline_tile=serve_cfg.pipeline_tile)
    # None = keep the index class's own tile defaults (they differ
    # between the flat engines and the IVF slab kernels)
    if serve_cfg.block_q is not None:
        opts["block_q"] = serve_cfg.block_q
    if serve_cfg.block_n is not None:
        opts["block_n"] = serve_cfg.block_n
    if index_cfg.kind != "flat":
        opts["refine_cap"] = index_cfg.refine_cap
    if index_cfg.kind == "ivf":
        if emb_db is None:
            raise ConfigError("index.kind='ivf' needs emb_db= (the "
                              "embeddings the codes encode) to fit the "
                              "coarse quantizer")
        opts.update(emb_db=emb_db, n_lists=index_cfg.n_lists,
                    n_probe=index_cfg.n_probe,
                    kmeans_iters=index_cfg.kmeans_iters, key=key)
    return make_index(index_cfg.kind, jax.device_put(codes),
                      jax.device_put(C), structure, **opts)


def build_ann_engine(codes, C, structure, *, topk: int = 50,
                     backend: str = "auto", block_q=None, block_n=None,
                     query_chunk=None, index: str = "two-step", mesh=None,
                     emb_db=None, n_lists: int = 64, n_probe: int = 8,
                     refine_cap=None, key=None, lut_dtype: str = "f32",
                     code_bits: int = 8, pipeline: str = "off",
                     pipeline_tile=None,
                     resilience: Optional[ResilienceConfig] = None,
                     fault_injector=None):
    """Batched ANN serving entry: returns an ``AnnEngine`` — call it
    with an (nq, d) query batch for a ``repro.index.SearchResult``,
    and grow it in place with ``engine.add(new_vectors)`` (incremental
    encode + append, no retraining).

    This is the historical kwarg surface; the kwargs are folded into
    the api config tree (``IndexConfig`` + ``ServeConfig``) and routed
    through ``build_index`` — new code should build an ``ICQConfig``
    and use ``ICQSession`` / ``build_index`` directly (docs/api.md).

    ``index`` selects the implementation ("flat" | "two-step" | "ivf");
    "ivf" additionally needs ``emb_db`` (the database embeddings the
    codes encode) and takes ``n_lists`` / ``n_probe`` / ``key``.
    ``mesh`` (optional, with a "data" axis) shards the index for
    data-parallel serving.  ``codes`` stay device-resident across calls
    (packed uint8; widened at the kernel boundary).  ``backend`` follows
    the unified dispatch: "pallas" fused kernels on TPU, vectorized jnp
    elsewhere.  ``lut_dtype`` ("f32" | "int8") selects the crude-pass
    LUT precision (DESIGN.md §8; honored by the sharded engines too).
    ``code_bits`` (8 | 4) selects the code storage width — 4 serves the
    fast-scan nibble-packed layout (DESIGN.md §12, needs m <= 16).
    ``pipeline`` ("off" | "tiles" | "auto") enables the overlapped
    crude/refine tile executor (DESIGN.md §13); ``pipeline_tile``
    overrides its queries-per-tile default.  ``resilience`` /
    ``fault_injector`` configure the engine's failure behavior
    (docs/robustness.md).
    """
    # n_lists/n_probe only describe an IVF; for the flat kinds they were
    # historically ignored, so keep them out of the validated config
    index_cfg = (IndexConfig(kind=index, n_lists=n_lists, n_probe=n_probe,
                             refine_cap=refine_cap, code_bits=code_bits)
                 if index == "ivf"
                 else IndexConfig(kind=index, refine_cap=refine_cap,
                                  code_bits=code_bits))
    serve_cfg = ServeConfig(topk=topk, backend=backend, lut_dtype=lut_dtype,
                            query_chunk=query_chunk, block_q=block_q,
                            block_n=block_n, pipeline=pipeline,
                            pipeline_tile=pipeline_tile)
    idx = build_index(codes, C, structure, index_cfg=index_cfg,
                      serve_cfg=serve_cfg, emb_db=emb_db, key=key)
    return AnnEngine(idx, mesh=mesh, resilience=resilience,
                     fault_injector=fault_injector)


def load_ann_engine(path: str, *, mesh=None,
                    overrides: Optional[Dict[str, Any]] = None,
                    verify_checksums: Optional[bool] = None,
                    fault_injector=None) -> AnnEngine:
    """Open a saved artifact directory as a live serving engine.

    The artifacts must contain an index (``Artifacts.save`` with
    ``index=``); ``overrides`` applies dotted config overrides (e.g.
    ``{"serve.backend": "jnp"}``, ``{"index.n_probe": 16}``) before the
    index is rebuilt, so a saved index can be re-served with different
    engine options without re-exporting (``index.kind`` names the
    stored layout and is rejected).  ``mesh`` shards the loaded index
    for data-parallel serving, exactly like ``build_ann_engine(mesh=)``.

    ``verify_checksums`` forces the full per-tensor sha256 pass on load
    (None defers to the embedded config's
    ``resilience.verify_artifacts``); the engine inherits the embedded
    ``ResilienceConfig``.
    """
    if verify_checksums is None:
        # peek: the embedded config decides, unless the caller forces it
        art = Artifacts.load(path, overrides=overrides)
        if art.config.resilience.verify_artifacts:
            art = Artifacts.load(path, overrides=overrides,
                                 verify_checksums=True)
    else:
        art = Artifacts.load(path, overrides=overrides,
                             verify_checksums=verify_checksums)
    if art.index is None:
        raise ArtifactError(
            f"{path}: artifacts hold no index (model-only save); build "
            "one with ICQSession.index and save again")
    return AnnEngine(art.index, mesh=mesh,
                     resilience=art.config.resilience,
                     fault_injector=fault_injector)
