#!/usr/bin/env python3
"""Chip smoke test: the ICQ fit -> encode -> search -> serve path on a
TPU at SIFT1M scale, through the ``repro.api`` front door.

    python chip_smoke.py              # one chip: every phase
    python chip_smoke.py --chips 4    # four chips: the sharded path only

Data is SIFT1M-shaped and generated from ``--seed`` with
``data/pseudo_real.py`` (d=128, L2, a 100,000-row learn set, a
1,000,000-row base, 256 Zipf-skewed queries); the model and index
configuration is ``examples/configs/sift1m_smoke.json`` (K=8 codebooks
of m=256: 8-byte codes).  One chip runs every phase in this process:

  data         generate learn / base / queries on the host
  fit          ``icq_session(cfg).fit(learn)``
  two-step     ``session.index(base)`` (1M-row encode, ``encode.backend``
               auto) and nq=64 batches through its ``AnnEngine``
  ivf          the same for ``index.kind="ivf"`` (1024 lists, 16 probes)
  serve        requests through ``repro.serve.ServingLoop`` on the
               two-step engine

Each search phase checks its answer against the same index served by
the jnp engines (the plain reference) and against exact ground truth
(recall@10).  ``--chips 4`` builds the two-step index sharded over a
four-device mesh and checks its ids against the single-device jnp
engine's.

Every phase prints one JSON line (compile seconds summed from JAX's
backend-compile events, so persistent-cache hits show as lower
compile seconds).  On success the last line is
``{"ok": true, "device": {...}}``.  Any failure — no TPU, a failover to
jnp, a phase that raised, an answer that disagrees with its reference
— prints one line ``chip_smoke: FAIL: <reason>`` and exits 1.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
CONFIG = os.path.join(ROOT, "examples", "configs", "sift1m_smoke.json")
N_LEARN = 100_000
N_BASE = 1_000_000
N_QUERIES = 256
BATCH = 64                 # queries per search batch
K_EVAL = 10                # recall@10
MIN_AGREEMENT = 0.99       # id agreement with the reference engine
MIN_RECALL = 0.02          # 2000x chance (10 of 1M); catches garbage
                           # ids (seed 0 reads about 0.055 on a v5e)
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class SmokeFailure(Exception):
    """A check failed; the message is the one-line reason."""


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def require_tpu(chips: int):
    """The device list, or SmokeFailure when it is not ``chips`` TPUs."""
    import jax

    if jax.default_backend() != "tpu":
        raise SmokeFailure(f"no TPU: jax.default_backend() is "
                           f"{jax.default_backend()!r}")
    devices = jax.devices()
    if len(devices) < chips:
        raise SmokeFailure(f"--chips {chips} needs {chips} TPU devices, "
                           f"JAX sees {len(devices)}")
    return devices


def load_config():
    from repro.api import ICQConfig

    return ICQConfig.load(CONFIG)


class CompileClock:
    """Backend-compile seconds and persistent-cache hits, read from
    ``jax.monitoring`` so every jit in the process is counted."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.seconds += duration

    def _event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.hits += 1

    def run(self, fn):
        """``fn()`` -> (result, {wall_s, compile_s, cache_hits})."""
        s0, h0, t0 = self.seconds, self.hits, time.perf_counter()
        out = fn()
        return out, {"wall_s": round(time.perf_counter() - t0, 3),
                     "compile_s": round(self.seconds - s0, 3),
                     "cache_hits": self.hits - h0}


def make_data(seed: int):
    """SIFT1M-shaped learn / base / query sets from one seed."""
    from repro.data.pseudo_real import pseudo_sift, skewed_queries

    X, _, cid = pseudo_sift(n=N_LEARN + N_BASE, n_queries=1, d=128,
                            seed=seed)
    learn, base, base_cid = X[:N_LEARN], X[N_LEARN:], cid[N_LEARN:]
    queries, _ = skewed_queries(base, base_cid, N_QUERIES, seed=seed)
    return learn, base, queries


def search_all(search, queries):
    """Run ``queries`` through ``search`` in BATCH-row batches.
    Returns (ids (nq, k) numpy, last result, per-batch wall ms)."""
    import numpy as np

    ids, ms, res = [], [], None
    for s in range(0, queries.shape[0], BATCH):
        t0 = time.perf_counter()
        res = search(queries[s:s + BATCH])
        ids.append(np.asarray(res.indices))
        ms.append(round((time.perf_counter() - t0) * 1000.0, 3))
    return np.concatenate(ids, axis=0), res, ms


def agreement(a, b) -> float:
    import numpy as np

    return float(np.mean(np.asarray(a) == np.asarray(b)))


def check_ids(name, ids, n):
    import numpy as np

    if ids.shape != (N_QUERIES, K_EVAL) or \
            not np.all((ids >= 0) & (ids < n)):
        raise SmokeFailure(f"{name}: ids of shape {ids.shape} outside "
                           f"[0, {n})")


def search_hlo(searcher, queries) -> str:
    """The lowered program of one search batch (a Pallas kernel shows
    up in it as a ``tpu_custom_call``)."""
    import jax

    q = searcher.model.embed(jax.numpy.asarray(queries))
    return jax.jit(lambda x: searcher.index.search(x)).lower(q).as_text()


def serve_phase(clock, name, searcher, queries, gt):
    """Warm + time nq=64 batches through the searcher's engine and hold
    the answer to the jnp reference and to exact ground truth.  Returns
    the warm ids."""
    import jax

    from repro import eval as eval_mod
    from repro.api import AnnEngine

    engine = searcher.engine
    _, cold = clock.run(lambda: search_all(searcher.search, queries))
    ids, res, warm_ms = search_all(searcher.search, queries)
    check_ids(name, ids, searcher.n)
    backend = res.meta.backend
    failovers = engine.stats["failovers"]
    if failovers:
        raise SmokeFailure(f"{name}: {failovers} failover(s) to jnp")
    if backend != "pallas":
        raise SmokeFailure(f"{name}: served by {backend!r}, not 'pallas'")
    has_kernel = "tpu_custom_call" in search_hlo(searcher, queries[:BATCH])
    if not has_kernel:
        raise SmokeFailure(f"{name}: no tpu_custom_call in the search HLO")
    ref = AnnEngine(dataclasses.replace(searcher.index, backend="jnp"))
    ref_ids, ref_res, _ = search_all(
        lambda q: ref.search(searcher.model.embed(jax.numpy.asarray(q))),
        queries)
    agree = agreement(ids, ref_ids)
    recall = eval_mod.recall_at_k(ids[:, :K_EVAL], gt, K_EVAL)
    emit(name, n=searcher.n, d=int(searcher.index.C.shape[-1]),
         nq_batch=BATCH, backend=backend, failovers=failovers,
         search_hlo_has_tpu_custom_call=has_kernel,
         cold_compile_s=cold["compile_s"], cold_wall_s=cold["wall_s"],
         cache_hits=cold["cache_hits"], warm_batch_ms=warm_ms,
         recall_at_10=recall, ids_equal_jnp=bool(agree == 1.0),
         id_agreement_jnp=agree, ref_backend=ref_res.meta.backend,
         pass_rate=float(res.pass_rate))
    if agree < MIN_AGREEMENT:
        raise SmokeFailure(f"{name}: pallas ids agree with the jnp engine "
                           f"on {agree:.4f} < {MIN_AGREEMENT}")
    if recall < MIN_RECALL:
        raise SmokeFailure(f"{name}: recall@10 {recall:.4f} < {MIN_RECALL}")
    return ids


def run_one_chip(clock, cfg, learn, base, queries, gt, seed):
    import jax
    import numpy as np

    from repro.api import icq_session
    from repro.serve import ServingLoop, Tenant
    from repro.trainer import encode_database

    session = icq_session(cfg)
    _, t = clock.run(lambda: session.fit(learn,
                                         key=jax.random.PRNGKey(seed)))
    emit("fit", n_learn=learn.shape[0], **t)

    two_step, t = clock.run(lambda: session.index(base))
    emit("index_two_step", n=two_step.n, encode_backend=cfg.encode.backend,
         **t)
    # encode parity: the first encode chunk of the base again, through
    # the index's own encoder, on the pallas and the jnp ICM engines
    # (same codebooks, same PQ warm start)
    rows = session.model.embed(jax.numpy.asarray(base[:cfg.encode.chunk]))
    codes = {be: np.asarray(encode_database(
        rows, session.model.C, icm_iters=cfg.encode.icm_iters,
        chunk=cfg.encode.chunk, backend=be, pack=False))
        for be in ("pallas", "jnp")}
    stored = np.asarray(two_step.index.codes[:cfg.encode.chunk])

    def row_agreement(a, b):
        return float(np.mean(np.all(a == b, axis=1)))

    emit("encode_parity", rows=rows.shape[0],
         row_agreement_pallas_jnp=row_agreement(codes["pallas"],
                                                codes["jnp"]),
         row_agreement_stored_pallas=row_agreement(
             stored.astype(np.int32), codes["pallas"]))

    ids_two_step = serve_phase(clock, "search_two_step", two_step, queries,
                               gt)

    session.config = cfg.with_overrides({"index.kind": "ivf"})
    ivf, t = clock.run(lambda: session.index(base))
    emit("index_ivf", n=ivf.n, n_lists=cfg.index.n_lists,
         n_probe=cfg.index.n_probe, **t)
    serve_phase(clock, "search_ivf", ivf, queries, gt)

    # a few requests through the coalescing loop: 32 two-row requests
    # fill the 64-row tile; each answer must equal the direct search
    tenant = Tenant(name="sift1m", engine=two_step.engine,
                    model=session.model)
    with ServingLoop(tenant, tile=BATCH) as loop:
        loop.warm()
        t0 = time.perf_counter()
        futs = [loop.submit(queries[i:i + 2]) for i in range(0, BATCH, 2)]
        got = np.concatenate([np.asarray(f.result(timeout=600).indices)
                              for f in futs], axis=0)
        wall_ms = (time.perf_counter() - t0) * 1000.0
        stats = dict(loop.stats)
    agree = agreement(got, ids_two_step[:BATCH])
    emit("serve", requests=len(futs), rows=BATCH,
         batches=int(stats["batches"]), wall_ms=round(wall_ms, 3),
         id_agreement_direct=agree,
         failovers=two_step.engine.stats["failovers"])
    if agree < MIN_AGREEMENT:
        raise SmokeFailure(f"serve: coalesced ids agree with direct search "
                           f"on {agree:.4f} < {MIN_AGREEMENT}")
    if two_step.engine.stats["failovers"]:
        raise SmokeFailure("serve: the engine failed over to jnp")


def run_four_chips(clock, cfg, learn, base, queries, gt, seed):
    import jax

    from repro import eval as eval_mod
    from repro.api import AnnEngine, icq_session
    from repro.distributed.sharding import make_mesh_auto

    session = icq_session(cfg)
    _, t = clock.run(lambda: session.fit(learn,
                                         key=jax.random.PRNGKey(seed)))
    emit("fit", n_learn=learn.shape[0], **t)
    mesh = make_mesh_auto((4,), ("data",))
    sharded, t = clock.run(lambda: session.index(base, mesh=mesh))
    shard_devices = sorted(
        str(d) for d in sharded.engine.served.codes.sharding.device_set)
    emit("index_sharded", n=sharded.n, shard_devices=shard_devices, **t)
    if len(shard_devices) != 4:
        raise SmokeFailure(f"codes sit on {len(shard_devices)} devices, "
                           "not 4")
    _, cold = clock.run(lambda: search_all(sharded.search, queries))
    ids, res, warm_ms = search_all(sharded.search, queries)
    check_ids("search_sharded", ids, sharded.n)
    ref = AnnEngine(dataclasses.replace(sharded.index, backend="jnp"))
    ref_ids, _, _ = search_all(
        lambda q: ref.search(session.model.embed(jax.numpy.asarray(q))),
        queries)
    agree = agreement(ids, ref_ids)
    emit("search_sharded", shards=4, nq_batch=BATCH,
         backend=res.meta.backend, cold_compile_s=cold["compile_s"],
         cold_wall_s=cold["wall_s"], warm_batch_ms=warm_ms,
         recall_at_10=eval_mod.recall_at_k(ids[:, :K_EVAL], gt, K_EVAL),
         ids_equal_single_device=bool(agree == 1.0),
         id_agreement_single_device=agree)
    if agree < 1.0:
        raise SmokeFailure(f"sharded ids agree with the single-device jnp "
                           f"engine on {agree:.4f}, not 1.0")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: every phase on one chip; 4: the sharded "
                         "two-step path and its single-device comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        import repro.api  # noqa: F401
    except ImportError as e:
        raise SmokeFailure(f"the repro package is not next to this script "
                           f"({e})") from None
    devices = require_tpu(args.chips)
    from repro.compile_cache import CACHE_ENV, enable_compile_cache

    cache_dir = enable_compile_cache()
    clock = CompileClock()
    cfg = load_config()
    emit("setup", cache_dir=cache_dir,
         cache_dir_from_env=bool(os.environ.get(CACHE_ENV)),
         config=os.path.relpath(CONFIG, ROOT), seed=args.seed)

    (learn, base, queries), t = clock.run(lambda: make_data(args.seed))
    emit("data", n_learn=learn.shape[0], n_base=base.shape[0],
         d=base.shape[1], n_queries=queries.shape[0], **t)
    from repro import eval as eval_mod

    (gt, _), t = clock.run(lambda: eval_mod.ground_truth(base, queries,
                                                         K_EVAL))
    emit("ground_truth", k=K_EVAL, **t)

    if args.chips == 4:
        run_four_chips(clock, cfg, learn, base, queries, gt, args.seed)
    else:
        run_one_chip(clock, cfg, learn, base, queries, gt, args.seed)

    stats = devices[0].memory_stats() or {}
    emit("memory", peak_bytes_in_use=stats.get("peak_bytes_in_use"),
         bytes_limit=stats.get("bytes_limit"))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", flush=True)
    except Exception as e:                       # noqa: BLE001
        traceback.print_exc()                    # the full story: stderr
        print(f"chip_smoke: FAIL: {type(e).__name__}: "
              f"{str(e).splitlines()[0] if str(e) else ''}", flush=True)
    sys.exit(1)
