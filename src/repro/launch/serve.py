"""Batched serving driver: prefill a prompt batch, then decode tokens.

Exercises the same prefill/decode_step the dry-run lowers at pod scale,
executing for real on the available devices (CPU smoke sizes).  The
``--icq-kv`` flag switches decode attention to the ICQ two-step
quantized KV cache (repro.quant.kv_cache) for dense-attention archs and
reports the achieved cache-byte reduction.

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --smoke --prompt-len 32 --decode-steps 8 --batch 2

``--ann`` serves the unified ANN index layer instead (no LM): a
synthetic packed-uint8 index is built and query batches stream through
the front-door api (``repro.api.build_ann_engine``, docs/api.md),
reporting per-query latency, pass rate, and Average Ops.  The run is
driven by an api config tree — ``--config path.json`` loads one, and
the engine flags (``--ann-index``, ``--ann-backend``, ``--ann-lists``,
``--ann-probe``, ``--lut-dtype``, ``--code-bits``, ``--ann-m``) are
dotted overrides on top of it (``--code-bits 4`` serves the
nibble-packed fast-scan layout, DESIGN.md §12 — pair it with
``--ann-m 16`` or a config whose ``train.codebook_size`` <= 16).
``--save-artifacts DIR`` persists the built index
(``repro.api.Artifacts``); ``--load-artifacts DIR`` serves a saved
directory in a fresh process instead of building one.  ``--ann-shards
N`` serves the index sharded over an N-way ``data`` mesh (run under
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` on CPU):

    PYTHONPATH=src python -m repro.launch.serve --ann --ann-n 100000 \
        --ann-queries 64 --ann-backend jnp
    PYTHONPATH=src python -m repro.launch.serve --ann \
        --save-artifacts /tmp/ann && \
        PYTHONPATH=src python -m repro.launch.serve \
        --load-artifacts /tmp/ann
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python -m repro.launch.serve --ann \
        --ann-index ivf --ann-shards 4 --ann-n 20000

``--serve-loop`` serves one or more saved artifact directories through
the async coalescing loop (``repro.serve.ServingLoop``, docs/serving.md)
under a short seeded Poisson workload instead of fixed query batches:
each repeatable ``--tenant NAME=DIR`` loads one Artifacts dir as a
tenant (a bare ``--load-artifacts DIR`` joins the loop as tenant
``default``), duplicate names or paths fail up front with a one-line
error, and ``--batch-window-ms`` / ``--batch-tile`` override every
tenant's coalescing knobs.  Per-tenant p50/p99 latency, QPS, and tile
fill are reported:

    PYTHONPATH=src python -m repro.launch.serve --serve-loop \
        --tenant prod=/tmp/ann_a --tenant canary=/tmp/ann_b \
        --batch-window-ms 2 --batch-tile 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, smoke_config
from repro.launch.steps import build_serve_fns


def _serve_mesh(shards: int):
    if shards <= 1:
        return None
    if len(jax.devices()) < shards:
        raise SystemExit(
            f"--ann-shards {shards} needs {shards} devices but only "
            f"{len(jax.devices())} are visible; on CPU set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={shards}")
    from repro.distributed.sharding import make_mesh_auto
    return make_mesh_auto((shards,), ("data",))


def _serve_batches(engine, nq: int, d: int, batches: int, label: str):
    """Warm + time ``batches`` random query batches through ``engine``."""
    qkey = jax.random.fold_in(jax.random.PRNGKey(0), 2)
    queries = jax.random.normal(qkey, (nq, d))
    res = engine(queries)                      # compile + warm
    jax.block_until_ready(res.indices)
    t0 = time.time()
    for i in range(batches):
        q = jax.random.normal(jax.random.fold_in(qkey, i), (nq, d))
        res = engine(q)
        jax.block_until_ready(res.indices)
    dt = (time.time() - t0) / batches
    print(f"{label}: {dt * 1e6 / nq:.1f} us/query "
          f"(batch {dt * 1e3:.1f} ms), pass_rate={float(res.pass_rate):.3f}, "
          f"avg_ops={float(res.avg_ops):.2f}")
    return queries, res


def serve_ann(cfg, n: int, nq: int, *, batches: int = 3, shards: int = 1,
              n_add: int = 0, save_dir=None):
    """Synthetic ANN serving loop through the front-door api: the config
    tree's ``train`` section fixes the synthetic index geometry, the
    ``index``/``serve`` sections drive construction and the engine
    (``repro.api.build_ann_engine``).

    ``n_add`` > 0 additionally exercises the incremental build surface:
    after the timed batches, ``n_add`` fresh vectors are encoded and
    appended via ``AnnEngine.add`` (ICM engine, no retraining; sharded
    engines re-shard the grown source index) and one more query batch
    is served from the grown index.  ``save_dir`` persists the built
    index (index-only artifacts) for ``--load-artifacts``."""
    from repro.api import Artifacts, build_ann_engine
    from repro.data.synthetic import make_synthetic_index

    d, K, m = cfg.train.d, cfg.train.num_codebooks, cfg.train.codebook_size
    key = jax.random.PRNGKey(0)
    codes, C, structure = make_synthetic_index(key, n, d=d, K=K, m=m,
                                               num_fast=cfg.train.num_fast)
    mesh = _serve_mesh(shards)
    emb_db = None
    if cfg.index.kind == "ivf":
        from repro.core import codebooks as cb
        emb_db = cb.decode(C, codes)          # reconstructed db embeddings
    engine = build_ann_engine(codes, C, structure, topk=cfg.serve.topk,
                              backend=cfg.serve.backend,
                              index=cfg.index.kind, mesh=mesh,
                              emb_db=emb_db, n_lists=cfg.index.n_lists,
                              n_probe=cfg.index.n_probe,
                              query_chunk=cfg.serve.query_chunk,
                              lut_dtype=cfg.serve.lut_dtype,
                              code_bits=cfg.index.code_bits,
                              pipeline=cfg.serve.pipeline,
                              pipeline_tile=cfg.serve.pipeline_tile,
                              key=jax.random.fold_in(key, 1))
    queries, _ = _serve_batches(
        engine, nq, d, batches,
        f"ann: index={cfg.index.kind} n={n} nq={nq} topk={cfg.serve.topk} "
        f"backend={cfg.serve.backend} lut={cfg.serve.lut_dtype} "
        f"bits={cfg.index.code_bits} pipeline={cfg.serve.pipeline} "
        f"shards={shards}")

    if n_add > 0:
        from repro.core import codebooks as cb
        new_codes = jax.random.randint(jax.random.fold_in(key, 3),
                                       (n_add, K), 0, m)
        new_vecs = cb.decode(C, new_codes) + 0.01 * jax.random.normal(
            jax.random.fold_in(key, 4), (n_add, d))
        t0 = time.time()
        engine.add(new_vecs)
        dt_add = time.time() - t0
        res2 = engine(queries)
        jax.block_until_ready(res2.indices)
        print(f"ann-add: +{n_add} vectors in {dt_add * 1e3:.1f} ms "
              f"(encode+append, no retrain) -> n={engine.n}; "
              f"post-add pass_rate={float(res2.pass_rate):.3f}")

    if save_dir:
        path = Artifacts(config=cfg, index=engine.index).save(save_dir)
        print(f"ann: artifacts (config hash {cfg.config_hash()[:12]}) "
              f"-> {path}; reload with --load-artifacts")


def serve_loaded(path: str, nq: int, *, batches: int = 3, shards: int = 1,
                 overrides=None, verify: bool = False):
    """Serve a saved artifact directory end-to-end: load + verify the
    manifest, rebuild the index (``repro.api.load_ann_engine``), and
    stream random query batches through it — the fresh-process half of
    the fit→save→load→search contract (CI runs this against artifacts
    written by ``launch/train.py --save-artifacts`` and by
    ``--ann --save-artifacts``).

    ``verify`` forces the full per-tensor sha256 pass
    (``--verify-artifacts``, docs/robustness.md).  Malformed artifacts
    — missing directory, missing/truncated files, checksum mismatches —
    exit with a one-line actionable error instead of a traceback."""
    from repro.api import ArtifactError, load_ann_engine

    try:
        engine = load_ann_engine(path, mesh=_serve_mesh(shards),
                                 overrides=overrides or None,
                                 verify_checksums=verify or None)
    except (ArtifactError, FileNotFoundError, OSError) as e:
        # the artifact layer's messages already name the file and the
        # expected-vs-found sizes/hashes — surface them, not the stack
        raise SystemExit(f"--load-artifacts {path}: {e}") from e
    d = engine.index.C.shape[-1]
    print(f"loaded artifacts {path}: index n={engine.n} d={d} "
          f"(kind from manifest)")
    _serve_batches(engine, nq, d, batches,
                   f"ann-loaded: n={engine.n} nq={nq} shards={shards}")


def serve_traffic(specs, *, rate_hz: float, duration_s: float,
                  window_ms=None, tile=None, shards: int = 1,
                  overrides=None, seed: int = 0, pool_q: int = 64):
    """Serve tenant artifact dirs through the coalescing loop under a
    seeded Poisson workload (``--serve-loop``; docs/serving.md).

    Spec conflicts — duplicate tenant names, two specs resolving to the
    same Artifacts directory — and artifact errors exit with a one-line
    actionable message instead of a traceback (or a silent double
    load)."""
    from repro.api import ArtifactError
    from repro.serve import (ServeError, ServingLoop, load_tenants,
                             make_workload, run_open_loop, summarize)

    try:
        tenants = load_tenants(specs, mesh=_serve_mesh(shards),
                               overrides=overrides or None)
    except (ServeError, ArtifactError, FileNotFoundError, OSError) as e:
        raise SystemExit(f"--serve-loop: {e}") from e
    rng = np.random.default_rng(seed)
    pools = {name: rng.standard_normal((pool_q, t.d)).astype(np.float32)
             for name, t in sorted(tenants.items())}
    workload = make_workload(pools, rate_hz, duration_s, rng=rng)
    with ServingLoop(tenants, window_ms=window_ms, tile=tile) as loop:
        for name in tenants:
            loop.warm(name)
        t0 = time.time()
        records = run_open_loop(loop, workload)
        wall_s = time.time() - t0
        stats = dict(loop.stats)
    for name in sorted(tenants):
        s = summarize([r for r in records if r["tenant"] == name],
                      wall_s=wall_s)
        if not s["requests"]:
            print(f"serve-loop[{name}]: no arrivals this run")
            continue
        print(f"serve-loop[{name}]: {s['requests']} req, "
              f"p50 {s['p50_ms']:.2f} ms, p99 {s['p99_ms']:.2f} ms, "
              f"{s['qps']:.1f} qps, fill {s['mean_batch_fill']:.2f}, "
              f"queue {s['mean_queue_ms']:.2f} ms")
    agg = summarize(records, wall_s=wall_s)
    print(f"serve-loop: {agg['requests']} req total, "
          f"{stats['batches']} flushes "
          f"(full {stats['flush_full']} / window {stats['flush_window']}), "
          f"p50 {agg['p50_ms']:.2f} ms, p99 {agg['p99_ms']:.2f} ms, "
          f"{agg['qps']:.1f} qps, degraded {agg['degraded_rate']:.2f}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--decode-steps", type=int, default=8)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--icq-kv", action="store_true")
    ap.add_argument("--ann", action="store_true",
                    help="serve the batched ANN index layer (no LM)")
    ap.add_argument("--config", default=None,
                    help="repro.api ICQConfig JSON driving the --ann run "
                         "(docs/api.md); the --ann-*/--lut-dtype flags "
                         "below override individual fields")
    ap.add_argument("--save-artifacts", default=None, metavar="DIR",
                    help="persist the --ann index (index-only artifacts); "
                         "reload with --load-artifacts DIR")
    ap.add_argument("--load-artifacts", default=None, metavar="DIR",
                    help="serve a saved artifact directory instead of "
                         "building one (repro.api.load_ann_engine); "
                         "engine flags act as overrides")
    ap.add_argument("--verify-artifacts", action="store_true",
                    help="with --load-artifacts: verify every tensor's "
                         "sha256 against the manifest before serving "
                         "(docs/robustness.md)")
    ap.add_argument("--ann-n", type=int, default=100_000)
    ap.add_argument("--ann-queries", type=int, default=64)
    ap.add_argument("--ann-backend", default=None,
                    choices=["auto", "jnp", "pallas"],
                    help="override serve.backend (config default: auto)")
    ap.add_argument("--ann-index", default=None,
                    choices=["flat", "two-step", "ivf"],
                    help="override index.kind (config default: two-step)")
    ap.add_argument("--ann-shards", type=int, default=1,
                    help="shard the index over an N-way data mesh")
    ap.add_argument("--ann-lists", type=int, default=None,
                    help="override index.n_lists (config default: 64)")
    ap.add_argument("--ann-probe", type=int, default=None,
                    help="override index.n_probe (config default: 8)")
    ap.add_argument("--lut-dtype", default=None, choices=["f32", "int8"],
                    help="override serve.lut_dtype (int8 = quantized "
                         "tables, DESIGN.md §8)")
    ap.add_argument("--code-bits", type=int, default=None, choices=[8, 4],
                    help="override index.code_bits (4 = nibble-packed "
                         "fast-scan codes, DESIGN.md §12; needs "
                         "codebook_size <= 16, e.g. --ann-m 16)")
    ap.add_argument("--pipeline", default=None,
                    choices=["off", "tiles", "auto"],
                    help="override serve.pipeline (tiles = overlap the "
                         "crude pass of one query tile with the refine "
                         "of the previous, DESIGN.md §13)")
    ap.add_argument("--pipeline-tile", type=int, default=None,
                    help="override serve.pipeline_tile (queries per "
                         "pipeline tile; default block_q on pallas, "
                         "16 on jnp)")
    ap.add_argument("--ann-m", type=int, default=None,
                    help="override train.codebook_size (the synthetic "
                         "index's codewords per codebook)")
    ap.add_argument("--ann-add", type=int, default=0,
                    help="after serving, grow the index by N vectors via "
                         "AnnEngine.add (incremental encode, DESIGN.md §9)")
    ap.add_argument("--serve-loop", action="store_true",
                    help="serve artifact tenants through the async "
                         "coalescing loop under a seeded Poisson workload "
                         "(repro.serve.ServingLoop, docs/serving.md)")
    ap.add_argument("--tenant", action="append", default=[],
                    metavar="NAME=DIR",
                    help="load an Artifacts dir as a named tenant of the "
                         "--serve-loop (repeatable); duplicate names or "
                         "paths are rejected up front")
    ap.add_argument("--batch-window-ms", type=float, default=None,
                    help="--serve-loop: override every tenant's "
                         "serve.batch_window_ms (max coalescing wait)")
    ap.add_argument("--batch-tile", type=int, default=None,
                    help="--serve-loop: override every tenant's "
                         "serve.batch_tile (rows per dispatched tile)")
    ap.add_argument("--serve-rate", type=float, default=50.0,
                    help="--serve-loop: Poisson arrival rate (req/s)")
    ap.add_argument("--serve-duration", type=float, default=1.0,
                    help="--serve-loop: workload duration (s)")
    ap.add_argument("--serve-seed", type=int, default=0,
                    help="--serve-loop: seed for arrivals + query rows")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    overrides = {k: v for k, v in {
        "serve.backend": args.ann_backend,
        "index.kind": args.ann_index,
        "index.n_lists": args.ann_lists,
        "index.n_probe": args.ann_probe,
        "serve.lut_dtype": args.lut_dtype,
        "index.code_bits": args.code_bits,
        "serve.pipeline": args.pipeline,
        "serve.pipeline_tile": args.pipeline_tile,
        "train.codebook_size": args.ann_m,
    }.items() if v is not None}

    if args.serve_loop:
        specs = list(args.tenant)
        if args.load_artifacts:
            # a bare --load-artifacts joins the loop as tenant
            # "default"; parse_tenant_specs then catches a --tenant
            # pointing at the same directory (or reusing the name)
            # with a one-line error instead of double-loading it
            specs = [f"default={args.load_artifacts}"] + specs
        if not specs:
            ap.error("--serve-loop needs at least one --tenant NAME=DIR "
                     "(or --load-artifacts DIR)")
        serve_traffic(specs, rate_hz=args.serve_rate,
                      duration_s=args.serve_duration,
                      window_ms=args.batch_window_ms,
                      tile=args.batch_tile, shards=args.ann_shards,
                      overrides=overrides, seed=args.serve_seed)
        return
    for flag, val in (("--tenant", args.tenant or None),
                      ("--batch-window-ms", args.batch_window_ms),
                      ("--batch-tile", args.batch_tile)):
        if val is not None:
            ap.error(f"{flag} requires --serve-loop")
    if args.load_artifacts:
        # flags that only make sense when *building* an index would be
        # silently ignored here — reject them instead
        for flag, val in (("--config", args.config),
                          ("--save-artifacts", args.save_artifacts),
                          ("--ann-add", args.ann_add or None),
                          ("--ann-index", args.ann_index)):
            if val is not None:
                ap.error(f"{flag} cannot be combined with "
                         "--load-artifacts (the artifacts embed their "
                         "own config and index layout); remaining "
                         "engine flags act as overrides")
        serve_loaded(args.load_artifacts, args.ann_queries,
                     shards=args.ann_shards, overrides=overrides,
                     verify=args.verify_artifacts)
        return
    if args.verify_artifacts:
        ap.error("--verify-artifacts only applies to --load-artifacts")
    if args.ann:
        from repro.api import ICQConfig

        cfg = (ICQConfig.load(args.config) if args.config
               else ICQConfig())
        serve_ann(cfg.with_overrides(overrides), args.ann_n,
                  args.ann_queries, shards=args.ann_shards,
                  n_add=args.ann_add, save_dir=args.save_artifacts)
        return
    if args.arch is None:
        ap.error("--arch is required unless --ann or --load-artifacts "
                 "is given")

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    prefill_fn, decode_fn, model = build_serve_fns(cfg)
    params = model.init(jax.random.PRNGKey(0))

    max_len = args.prompt_len + args.decode_steps
    rng = np.random.default_rng(0)
    b = args.batch
    s_text = args.prompt_len - (cfg.num_vision_tokens
                                if cfg.frontend == "vision_stub" else 0)
    batch = {"tokens": rng.integers(0, cfg.vocab_size, (b, s_text),
                                    dtype=np.int32)}
    if cfg.frontend == "vision_stub":
        batch["patch_emb"] = rng.standard_normal(
            (b, cfg.num_vision_tokens, cfg.vision_dim)).astype(np.float32)
    if cfg.encdec:
        batch["audio_emb"] = rng.standard_normal(
            (b, cfg.encoder_seq_len, cfg.d_model)).astype(np.float32)

    t0 = time.time()
    logits, caches = jax.jit(
        lambda p, bt: prefill_fn(p, bt, max_len))(params, batch)
    logits.block_until_ready()
    print(f"prefill: {args.prompt_len} tokens x {b} in "
          f"{time.time() - t0:.2f}s; logits {logits.shape}")

    decode_jit = jax.jit(decode_fn, donate_argnums=(2,))
    tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)[:, None]
    out_tokens = [np.asarray(tok)]
    t0 = time.time()
    for i in range(args.decode_steps):
        logits, caches = decode_jit(params, tok, caches)
        tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)[:, None]
        out_tokens.append(np.asarray(tok))
    jax.block_until_ready(caches)
    dt = time.time() - t0
    print(f"decode: {args.decode_steps} steps in {dt:.2f}s "
          f"({1e3 * dt / max(args.decode_steps, 1):.1f} ms/tok)")
    print("generated:", np.concatenate(out_tokens, axis=1)[:, :16])

    if args.icq_kv:
        from repro.quant import (ICQKVConfig, build_icq_kv_cache,
                                 icq_kv_decode_attention)
        from repro.quant.kv_cache import reference_decode_attention
        # standalone ICQ-KV demonstration on this arch's head geometry
        kvh = max(cfg.num_kv_heads, 1)
        dh = max(cfg.head_dim, 16)
        S = max_len
        key = jax.random.PRNGKey(1)
        k = jax.random.normal(key, (b, S, kvh, dh))
        v = jax.random.normal(jax.random.fold_in(key, 1), (b, S, kvh, dh))
        q = jax.random.normal(jax.random.fold_in(key, 2),
                              (b, 1, cfg.num_heads or kvh, dh))
        kvcfg = ICQKVConfig(d_fast=max(dh // 4, 4))
        cache = build_icq_kv_cache(kvcfg, k, v, max_len=S)
        out = icq_kv_decode_attention(q, cache, kvcfg, S - 1,
                                      top_c=max(S // 8, 4))
        ref = reference_decode_attention(q, k, v, S - 1)
        err = float(jnp.abs(out - ref).max())
        raw = S * kvh * dh * 2 * 2                       # bf16 K+V
        icq = (S * kvh * kvcfg.d_fast * 2                # crude reads
               + (S // 8) * kvh * dh * 2 * 1)            # int8 survivors
        print(f"icq-kv: max err {err:.4f}; decode HBM bytes/head "
              f"{raw} -> {icq} ({raw / icq:.1f}x less)")


if __name__ == "__main__":
    main()
