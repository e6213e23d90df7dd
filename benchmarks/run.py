"""Benchmark entry point: one section per paper figure + kernel
microbenchmarks + the engine benchmarks for cross-PR perf tracking —
batched search (``BENCH_search.json``), batched IVF
(``BENCH_ivf.json``), quantized LUTs (``BENCH_lutq.json``), the 4-bit
fast-scan crude pass (``BENCH_fastscan.json``), the tiled ICM encoding
engine (``BENCH_encode.json``), and the scan-compiled trainer
(``BENCH_train.json``) — plus the roofline table (if dry-run artifacts
exist).  See docs/benchmarks.md for every ``--only`` target.

Engine targets accept ``--config path.json`` (a ``repro.api.ICQConfig``,
docs/api.md) pinning geometry and engine options, so a BENCH run is
reproducible from a checked-in config
(``benchmarks/configs/bench_small.json``).

    PYTHONPATH=src python -m benchmarks.run [--full] [--only fig3]
    PYTHONPATH=src python -m benchmarks.run --only search   # just the JSON
    PYTHONPATH=src python -m benchmarks.run --only ivf \
        --config benchmarks/configs/bench_small.json
    PYTHONPATH=src python -m benchmarks.run --only ivf      # BENCH_ivf.json
    PYTHONPATH=src python -m benchmarks.run --only lutq     # BENCH_lutq.json
    PYTHONPATH=src python -m benchmarks.run --only fastscan # BENCH_fastscan.json
    PYTHONPATH=src python -m benchmarks.run --only encode   # BENCH_encode.json
    PYTHONPATH=src python -m benchmarks.run --only train    # BENCH_train.json
    PYTHONPATH=src python -m benchmarks.run --only faults   # BENCH_faults.json
    PYTHONPATH=src python -m benchmarks.run --only pipeline # BENCH_pipeline.json
    PYTHONPATH=src python -m benchmarks.run --only pareto   # BENCH_pareto.json
    PYTHONPATH=src python -m benchmarks.run --only serve    # BENCH_serve.json

Every target accepts ``--seed N`` (default 0), threaded through its
data generation — two same-seed runs report identical recall numbers.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import (beyond_ivf, fig1_synthetic_pq, fig2_synthetic_cq,
                        fig3_realworld_sq, fig4_code_length, fig5_pqn,
                        fig6_unseen, serve_load, sweep)
from benchmarks.common import header, host_copy


def search_bench(full: bool = False, *, out_path: str = "BENCH_search.json",
                 n: int = 100_000, nq: int = 64, K: int = 8, m: int = 256,
                 num_fast: int = 2, topk: int = 50, d: int = 16,
                 repeats: int = 3, pallas_n: int = 4096, pallas_nq: int = 8,
                 seed: int = 0):
    """Batched two-step engine vs the per-query ``lax.map`` baseline on a
    synthetic index (n points, nq-query batches), written to
    ``out_path`` so the perf trajectory is machine-readable across PRs.

    The pallas row runs interpret mode (CPU container) at a reduced size
    — it tracks correctness/call overhead, not TPU latency.  ``seed``
    drives every PRNG key (data + queries): two runs with the same seed
    report identical recall/avg_ops numbers.
    """
    from repro.core.search import two_step_search
    from repro.data.synthetic import make_synthetic_index
    from repro.kernels.ref import two_step_search_looped

    if full:
        n, nq = max(n, 1_000_000), max(nq, 256)
    key = jax.random.PRNGKey(seed)
    codes, C, structure = make_synthetic_index(key, n, d=d, K=K, m=m,
                                               num_fast=num_fast)
    queries = jax.random.normal(jax.random.fold_in(key, 2), (nq, d))

    def timed(fn, *args, **kw):
        # host_copy releases the warm result's device buffers so the
        # timed calls reuse the top-k carry instead of re-allocating it;
        # min-of-repeats: see ivf_bench (cpu-share throttled container)
        res = host_copy(fn(*args, **kw))             # compile + warm
        ts = []
        for _ in range(repeats):
            t0 = time.time()
            jax.block_until_ready(fn(*args, **kw).indices)
            ts.append(time.time() - t0)
        return res, min(ts)

    rows = []
    lax_fn = jax.jit(
        lambda q: two_step_search_looped(q, codes, C, structure, topk))
    jnp_fn = jax.jit(
        lambda q: two_step_search(q, codes, C, structure, topk,
                                  backend="jnp"))
    # the batched-vs-laxmap ratio is the headline: interleave the two
    # engines and take the median of paired ratios (see lutq_bench —
    # common-mode cpu-share interference cancels inside each pair);
    # per-row latencies report min-of-repeats like the other benches
    res_l = host_copy(lax_fn(queries))               # compile + warm,
    res_b = host_copy(jnp_fn(queries))               # buffers released
    ts_l, ts_b = [], []
    for _ in range(3 * repeats):
        t0 = time.time()
        jax.block_until_ready(lax_fn(queries).indices)
        ts_l.append(time.time() - t0)
        t0 = time.time()
        jax.block_until_ready(jnp_fn(queries).indices)
        ts_b.append(time.time() - t0)
    dt_l, dt_b = min(ts_l), min(ts_b)
    pair_ratios = sorted(l / b for l, b in zip(ts_l, ts_b))
    speedup = pair_ratios[len(pair_ratios) // 2]
    rows.append(dict(backend="lax_map", n=n, nq=nq,
                     search_us=round(dt_l / nq * 1e6, 2),
                     avg_ops=round(float(res_l.avg_ops), 4),
                     pass_rate=round(float(res_l.pass_rate), 4)))
    rows.append(dict(backend="jnp", n=n, nq=nq,
                     search_us=round(dt_b / nq * 1e6, 2),
                     avg_ops=round(float(res_b.avg_ops), 4),
                     pass_rate=round(float(res_b.pass_rate), 4)))
    # pallas interpret: reduced size, correctness/overhead tracking only
    codes_s, queries_s = codes[:pallas_n], queries[:pallas_nq]
    res_p, dt_p = timed(
        lambda q: two_step_search(q, codes_s, C, structure, topk,
                                  backend="pallas", interpret=True),
        queries_s)
    rows.append(dict(backend="pallas_interpret", n=pallas_n, nq=pallas_nq,
                     search_us=round(dt_p / pallas_nq * 1e6, 2),
                     avg_ops=round(float(res_p.avg_ops), 4),
                     pass_rate=round(float(res_p.pass_rate), 4)))

    out = dict(topk=topk, K=K, m=m, num_fast=num_fast, d=d,
               rows=rows,
               speedup_batched_vs_laxmap=round(speedup, 3))
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    for r in rows:
        print(f"search,{r['backend']},n={r['n']},nq={r['nq']},,"
              f"{r['avg_ops']},{r['pass_rate']},,{r['search_us']}",
              flush=True)
    print(f"# batched-vs-laxmap speedup {out['speedup_batched_vs_laxmap']}x"
          f" -> {out_path}", flush=True)
    return out


def ivf_bench(full: bool = False, *, out_path: str = "BENCH_ivf.json",
              n: int = 100_000, nq: int = 64, K: int = 8, m: int = 256,
              num_fast: int = 2, topk: int = 50, d: int = 16,
              n_lists: int = 256, probes=(4, 8, 16), repeats: int = 9,
              query_chunk: int = 32, pallas_n_probe: int = 4,
              pallas_nq: int = 8, seed: int = 0):
    """Batched IVF engine vs the per-query ``lax.map`` IVF baseline
    (and the flat two-step engine) on a synthetic index, written to
    ``out_path`` for cross-PR perf tracking.

    Reports us/query and recall@10 (vs exact L2 over the reconstructed
    database) per n_probe and per shard count.  Shard rows require >1
    visible device (CPU: XLA_FLAGS=--xla_force_host_platform_device_
    count=N); with one device only shards=1 is recorded.
    """
    from benchmarks.common import engine_ground_truth, recall_at_k
    from repro.core import codebooks as cb
    from repro.core.search import two_step_search
    from repro.data.synthetic import make_synthetic_index
    from repro.index import (IVFTwoStep, build_ivf, ivf_list_codes,
                             ivf_two_step_search)
    from repro.kernels.ref import ivf_two_step_search_looped

    if full:
        n, nq = max(n, 1_000_000), max(nq, 256)
    key = jax.random.PRNGKey(seed)
    codes, C, structure = make_synthetic_index(key, n, d=d, K=K, m=m,
                                               num_fast=num_fast)
    emb_db = cb.decode(C, codes)                 # reconstructed db points
    queries = jax.random.normal(jax.random.fold_in(key, 2), (nq, d))
    ivf = build_ivf(jax.random.fold_in(key, 3), emb_db, n_lists)
    slab = ivf_list_codes(ivf, codes)
    # recall@10 vs the *full quantized ADC ranking* — see
    # benchmarks.common.engine_ground_truth for why not exact-L2
    gt = engine_ground_truth(queries, codes, C, 10)

    def timed(fn, *args, **kw):
        # min-of-repeats: this container is cpu-share throttled and
        # mean/median of few wall times swing 2-3x between runs; the
        # minimum tracks the interference-free cost.  host_copy releases
        # the warm result's buffers so the timed calls reuse the top-k
        # carry instead of re-allocating it every batch.
        res = host_copy(fn(*args, **kw))         # compile + warm
        ts = []
        for _ in range(repeats):
            t0 = time.time()
            jax.block_until_ready(fn(*args, **kw).indices)
            ts.append(time.time() - t0)
        return res, min(ts)

    def row(engine, n_probe, shards, res, dt, n_run=n, nq_run=nq):
        return dict(engine=engine, n=n_run, nq=nq_run, n_probe=n_probe,
                    shards=shards,
                    search_us=round(dt / nq_run * 1e6, 2),
                    recall10=round(recall_at_k(res.indices[:, :10],
                                               gt[:nq_run], 10), 4),
                    avg_ops=round(float(res.avg_ops), 4),
                    pass_rate=round(float(res.pass_rate), 4))

    rows = []
    # per-query lax.map baseline (the retired formulation) at the
    # headline probe count (8 when swept, else the largest probe)
    headline = 8 if 8 in probes else probes[-1]
    res_l, dt_l = timed(jax.jit(
        lambda q: ivf_two_step_search_looped(q, codes, C, structure, ivf,
                                             topk, headline)), queries)
    rows.append(row("ivf_lax_map", headline, 1, res_l, dt_l))
    # batched jnp engine across the probe sweep
    dt_bh, recall_gap = None, None
    for n_probe in probes:
        res_b, dt_b = timed(jax.jit(
            lambda q, p=n_probe: ivf_two_step_search(
                q, codes, C, structure, ivf, topk, p, backend="jnp",
                list_codes=slab, query_chunk=query_chunk)), queries)
        rows.append(row("ivf_batched_jnp", n_probe, 1, res_b, dt_b))
        if n_probe == headline:
            dt_bh = dt_b
            recall_gap = abs(rows[0]["recall10"] - rows[-1]["recall10"])
    # flat two-step engine for context (the BENCH_search.json hot path)
    res_f, dt_f = timed(jax.jit(
        lambda q: two_step_search(q, codes, C, structure, topk,
                                  backend="jnp")), queries)
    rows.append(row("flat_two_step_jnp", None, 1, res_f, dt_f))
    # pallas interpret: reduced size, correctness/overhead tracking only
    q_s = queries[:pallas_nq]
    res_p, dt_p = timed(
        lambda q: ivf_two_step_search(q, codes, C, structure, ivf, topk,
                                      pallas_n_probe, backend="pallas",
                                      interpret=True), q_s)
    rows.append(row("ivf_pallas_interpret", pallas_n_probe, 1, res_p, dt_p,
                    nq_run=pallas_nq))
    # sharded serving (needs >1 visible device)
    n_dev = len(jax.devices())
    if n_dev > 1:
        mesh = jax.make_mesh((n_dev,), ("data",))
        idx = IVFTwoStep(codes=codes, C=C, structure=structure, ivf=ivf,
                         n_probe=headline, topk=topk,
                         backend="jnp").shard(mesh)
        res_s, dt_s = timed(idx.search, queries)
        rows.append(row("ivf_batched_jnp", headline, n_dev, res_s, dt_s))
    else:
        print("# ivf: 1 device visible — skipping shard rows (set "
              "XLA_FLAGS=--xla_force_host_platform_device_count=N)",
              flush=True)

    out = dict(topk=topk, K=K, m=m, num_fast=num_fast, d=d,
               n_lists=n_lists, imbalance=round(ivf.imbalance, 3),
               rows=rows,
               headline_probe=headline,
               speedup_batched_vs_laxmap_probe8=round(dt_l / dt_bh, 3),
               recall10_gap_probe8=round(recall_gap, 4))
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    for r in rows:
        print(f"ivf,{r['engine']},n={r['n']},nq={r['nq']},"
              f"probe={r['n_probe']},shards={r['shards']},"
              f"recall10={r['recall10']},{r['avg_ops']},{r['pass_rate']},"
              f"{r['search_us']}", flush=True)
    print(f"# ivf batched-vs-laxmap speedup "
          f"{out['speedup_batched_vs_laxmap_probe8']}x (recall gap "
          f"{out['recall10_gap_probe8']}) -> {out_path}", flush=True)
    return out


def lutq_bench(full: bool = False, *, out_path: str = "BENCH_lutq.json",
               n: int = 100_000, nq: int = 64, K: int = 8, m: int = 256,
               num_fast: int = 2, topk: int = 50, d: int = 16,
               repeats: int = 9, pallas_n: int = 4096, pallas_nq: int = 8,
               seed: int = 0):
    """Quantized-LUT (int8) crude pass vs the f32 crude pass on the jnp
    backend, plus end-to-end two-step rows per ``lut_dtype`` and a
    pallas-interpret int8 tracking row, written to ``out_path``
    (DESIGN.md §8).

    The crude-pass rows time exactly the phase-1 work — LUT build
    (+ int8 calibration) and the fast-masked LUT sum over all n points;
    the int8 row's narrow integer accumulation is the memory-traffic
    win being tracked.  recall@10 is measured against the full f32 ADC
    ranking (random synthetic codes make exact-L2 recall meaningless
    for engine comparisons) for the f32 and int8 two-step engines; the
    acceptance gate is a delta <= 0.01.
    """
    from benchmarks.common import engine_ground_truth, recall_at_k
    from repro.core.search import two_step_search
    from repro.data.synthetic import make_synthetic_index
    from repro.index.base import build_lut, lut_sum, quantize_lut

    if full:
        n, nq = max(n, 1_000_000), max(nq, 256)
    key = jax.random.PRNGKey(seed)
    codes, C, structure = make_synthetic_index(key, n, d=d, K=K, m=m,
                                               num_fast=num_fast)
    queries = jax.random.normal(jax.random.fold_in(key, 2), (nq, d))
    fast = structure.fast_mask
    codes_i32 = codes.astype(jnp.int32)
    gt = engine_ground_truth(queries, codes, C, 10)

    def timed(fn, *args):
        out = host_copy(fn(*args))               # compile + warm, release
        ts = []
        for _ in range(repeats):
            t0 = time.time()
            jax.block_until_ready(fn(*args))
            ts.append(time.time() - t0)
        # min-of-repeats: see ivf_bench (cpu-share throttled container)
        return out, min(ts)

    @jax.jit
    def crude_f32(q):
        return lut_sum(build_lut(q, C), codes_i32, fast)

    @jax.jit
    def crude_int8(q):
        return lut_sum(quantize_lut(build_lut(q, C), fast), codes_i32, fast)

    rows = []
    # the crude-pass ratio is the headline: *interleave* the f32/int8
    # measurements so a cpu-share spike hits adjacent samples of both
    # engines equally (back-to-back phases measured ratio swings of 2x+
    # on this throttled container), then take the *median of paired
    # ratios* — common-mode interference cancels inside each pair, so
    # the estimate tracks the engines' true relative cost; per-row
    # latencies still report min-of-repeats like the other benches
    ref = host_copy(crude_f32(queries))          # compile + warm both,
    out = host_copy(crude_int8(queries))         # buffers released
    ts_f, ts_q = [], []
    for _ in range(3 * repeats):
        t0 = time.time()
        jax.block_until_ready(crude_f32(queries))
        ts_f.append(time.time() - t0)
        t0 = time.time()
        jax.block_until_ready(crude_int8(queries))
        ts_q.append(time.time() - t0)
    dt_f, dt_q = min(ts_f), min(ts_q)
    pair_ratios = sorted(f / q for f, q in zip(ts_f, ts_q))
    crude_speedup = pair_ratios[len(pair_ratios) // 2]
    rows.append(dict(stage="crude", lut_dtype="f32", n=n, nq=nq,
                     search_us=round(dt_f / nq * 1e6, 2)))
    max_err = float(jnp.max(jnp.abs(out - ref)))
    rows.append(dict(stage="crude", lut_dtype="int8", n=n, nq=nq,
                     search_us=round(dt_q / nq * 1e6, 2),
                     max_abs_err=round(max_err, 5)))

    recalls = {}
    for lut_dtype in ("f32", "int8"):
        res, dt = timed(jax.jit(
            lambda q, lt=lut_dtype: two_step_search(
                q, codes, C, structure, topk, backend="jnp",
                lut_dtype=lt)), queries)
        recalls[lut_dtype] = recall_at_k(res.indices[:, :10], gt, 10)
        rows.append(dict(stage="two_step", lut_dtype=lut_dtype, n=n, nq=nq,
                         search_us=round(dt / nq * 1e6, 2),
                         recall10=round(recalls[lut_dtype], 4),
                         avg_ops=round(float(res.avg_ops), 4),
                         pass_rate=round(float(res.pass_rate), 4)))
    # pallas interpret: reduced size, correctness/overhead tracking only
    codes_s, q_s = codes[:pallas_n], queries[:pallas_nq]
    res_p, dt_p = timed(lambda q: two_step_search(
        q, codes_s, C, structure, topk, backend="pallas", interpret=True,
        lut_dtype="int8"), q_s)
    rows.append(dict(stage="two_step_pallas_interpret", lut_dtype="int8",
                     n=pallas_n, nq=pallas_nq,
                     search_us=round(dt_p / pallas_nq * 1e6, 2),
                     pass_rate=round(float(res_p.pass_rate), 4)))

    out = dict(topk=topk, K=K, m=m, num_fast=num_fast, d=d, rows=rows,
               speedup_crude_int8_vs_f32=round(crude_speedup, 3),
               recall10_f32=round(recalls["f32"], 4),
               recall10_int8=round(recalls["int8"], 4),
               recall10_delta=round(abs(recalls["f32"] - recalls["int8"]), 4))
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    for r in rows:
        print(f"lutq,{r['stage']},{r['lut_dtype']},n={r['n']},nq={r['nq']},"
              f"recall10={r.get('recall10', '')},{r['search_us']}",
              flush=True)
    print(f"# lutq crude int8-vs-f32 speedup "
          f"{out['speedup_crude_int8_vs_f32']}x (recall@10 delta "
          f"{out['recall10_delta']}) -> {out_path}", flush=True)
    return out


def fastscan_bench(full: bool = False, *,
                   out_path: str = "BENCH_fastscan.json",
                   n: int = 100_000, nq: int = 64, K: int = 8, m: int = 16,
                   num_fast: int = 2, topk: int = 50, d: int = 16,
                   repeats: int = 9, pallas_n: int = 4096,
                   pallas_nq: int = 8, seed: int = 0):
    """4-bit fast-scan crude pass (``code_bits=4``, DESIGN.md §12) vs
    the int8 and f32 crude passes on the jnp backend, written to
    ``out_path``.

    Geometry is pinned to ``m <= 16`` (nibble-addressable codebooks).
    The crude rows time exactly the phase-1 work — LUT build
    (+ calibration where quantized) and the fast-masked LUT sum over
    all n points; the 4-bit row reads half the code bytes and gathers
    per *packed byte* from a paired 256-entry table, which is the
    bandwidth win being tracked (acceptance gate: >= 1.3x vs the int8
    8-bit crude pass).  recall@10 is measured against the full f32 ADC
    ranking for the f32/8-bit and int8/4-bit two-step engines
    (acceptance gate: delta <= 0.01), and code-memory bytes per row are
    reported for both layouts.
    """
    from benchmarks.common import engine_ground_truth, recall_at_k
    from repro.core.encode import pack_nibbles
    from repro.core.search import two_step_search
    from repro.data.synthetic import make_synthetic_index
    from repro.index.base import (build_lut, lut_sum, nibble_lut_sum,
                                  quantize_lut)

    if full:
        n, nq = max(n, 1_000_000), max(nq, 256)
    key = jax.random.PRNGKey(seed)
    codes, C, structure = make_synthetic_index(key, n, d=d, K=K, m=m,
                                               num_fast=num_fast)
    queries = jax.random.normal(jax.random.fold_in(key, 2), (nq, d))
    fast = structure.fast_mask
    codes_i32 = codes.astype(jnp.int32)
    packed = pack_nibbles(codes, K)
    gt = engine_ground_truth(queries, codes, C, 10)

    def timed(fn, *args):
        out = host_copy(fn(*args))               # compile + warm, release
        ts = []
        for _ in range(repeats):
            t0 = time.time()
            jax.block_until_ready(fn(*args))
            ts.append(time.time() - t0)
        # min-of-repeats: see ivf_bench (cpu-share throttled container)
        return out, min(ts)

    @jax.jit
    def crude_f32(q):
        return lut_sum(build_lut(q, C), codes_i32, fast)

    @jax.jit
    def crude_int8(q):
        return lut_sum(quantize_lut(build_lut(q, C), fast), codes_i32, fast)

    @jax.jit
    def crude_nib(q):
        return nibble_lut_sum(quantize_lut(build_lut(q, C), fast), packed,
                              K, cb_mask=fast)

    # interleave all three crude variants and take medians of paired
    # ratios (see lutq_bench: common-mode cpu-share interference cancels
    # inside each round on this throttled container); per-row latencies
    # still report min-of-repeats like the other benches
    ref = host_copy(crude_f32(queries))          # compile + warm all,
    out8 = host_copy(crude_int8(queries))        # buffers released
    out4 = host_copy(crude_nib(queries))
    ts_f, ts_q, ts_n = [], [], []
    for _ in range(3 * repeats):
        t0 = time.time()
        jax.block_until_ready(crude_f32(queries))
        ts_f.append(time.time() - t0)
        t0 = time.time()
        jax.block_until_ready(crude_int8(queries))
        ts_q.append(time.time() - t0)
        t0 = time.time()
        jax.block_until_ready(crude_nib(queries))
        ts_n.append(time.time() - t0)
    dt_f, dt_q, dt_n = min(ts_f), min(ts_q), min(ts_n)

    def median_ratio(num, den):
        r = sorted(a / b for a, b in zip(num, den))
        return r[len(r) // 2]

    speedup_4bit_vs_int8 = median_ratio(ts_q, ts_n)
    speedup_4bit_vs_f32 = median_ratio(ts_f, ts_n)
    # the 4-bit kernel must be *bitwise* the int8 crude pass (same
    # calibration, same dequant expression; DESIGN.md §12)
    bitwise_4bit_vs_int8 = bool(jnp.all(out4 == out8))
    rows = [
        dict(stage="crude", variant="f32", n=n, nq=nq,
             search_us=round(dt_f / nq * 1e6, 2)),
        dict(stage="crude", variant="int8", n=n, nq=nq,
             search_us=round(dt_q / nq * 1e6, 2),
             max_abs_err=round(float(jnp.max(jnp.abs(out8 - ref))), 5)),
        dict(stage="crude", variant="int8_4bit", n=n, nq=nq,
             search_us=round(dt_n / nq * 1e6, 2),
             bitwise_match_int8=bitwise_4bit_vs_int8),
    ]

    recalls = {}
    for label, kw in (("f32_8bit", dict(lut_dtype="f32")),
                      ("int8_4bit", dict(lut_dtype="int8", code_bits=4))):
        cds = packed if kw.get("code_bits") == 4 else codes
        res, dt = timed(jax.jit(
            lambda q, c=cds, k=dict(kw): two_step_search(
                q, c, C, structure, topk, backend="jnp", **k)), queries)
        recalls[label] = recall_at_k(res.indices[:, :10], gt, 10)
        rows.append(dict(stage="two_step", variant=label, n=n, nq=nq,
                         search_us=round(dt / nq * 1e6, 2),
                         recall10=round(recalls[label], 4),
                         avg_ops=round(float(res.avg_ops), 4),
                         pass_rate=round(float(res.pass_rate), 4)))
    # pallas interpret: reduced size, correctness/overhead tracking only
    packed_s, codes_s, q_s = packed[:pallas_n], codes[:pallas_n], \
        queries[:pallas_nq]
    res_j = host_copy(two_step_search(q_s, packed_s, C, structure, topk,
                                      backend="jnp", lut_dtype="int8",
                                      code_bits=4))
    res_p, dt_p = timed(lambda q: two_step_search(
        q, packed_s, C, structure, topk, backend="pallas", interpret=True,
        lut_dtype="int8", code_bits=4), q_s)
    rows.append(dict(stage="two_step_pallas_interpret", variant="int8_4bit",
                     n=pallas_n, nq=pallas_nq,
                     search_us=round(dt_p / pallas_nq * 1e6, 2),
                     pass_rate=round(float(res_p.pass_rate), 4),
                     indices_match_jnp=bool(
                         jnp.all(res_p.indices == res_j.indices))))

    out = dict(topk=topk, K=K, m=m, num_fast=num_fast, d=d, rows=rows,
               bytes_per_row_8bit=K,
               bytes_per_row_4bit=(K + 1) // 2,
               speedup_crude_4bit_vs_int8=round(speedup_4bit_vs_int8, 3),
               speedup_crude_4bit_vs_f32=round(speedup_4bit_vs_f32, 3),
               bitwise_crude_4bit_vs_int8=bitwise_4bit_vs_int8,
               recall10_f32=round(recalls["f32_8bit"], 4),
               recall10_int8_4bit=round(recalls["int8_4bit"], 4),
               recall10_delta=round(abs(recalls["f32_8bit"]
                                        - recalls["int8_4bit"]), 4))
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    for r in rows:
        print(f"fastscan,{r['stage']},{r['variant']},n={r['n']},"
              f"nq={r['nq']},recall10={r.get('recall10', '')},"
              f"{r['search_us']}", flush=True)
    print(f"# fastscan crude 4bit-vs-int8 speedup "
          f"{out['speedup_crude_4bit_vs_int8']}x (vs f32 "
          f"{out['speedup_crude_4bit_vs_f32']}x, bitwise "
          f"{bitwise_4bit_vs_int8}, recall@10 delta "
          f"{out['recall10_delta']}, bytes/row {out['bytes_per_row_8bit']}"
          f"->{out['bytes_per_row_4bit']}) -> {out_path}", flush=True)
    return out


def encode_bench(full: bool = False, *, out_path: str = "BENCH_encode.json",
                 n: int = 100_000, d: int = 16, K: int = 8, m: int = 256,
                 iters: int = 3, chunk: int = 8192, repeats: int = 3,
                 point_chunk: int = 8192, pallas_n: int = 8192,
                 block_n: int = 1024, seed: int = 0):
    """Tiled ICM encoding engine vs the seed per-chunk host loop
    (cross-Gram formulation, ragged last chunk re-jitted), written to
    ``out_path`` for cross-PR perf tracking (DESIGN.md §9).

    The seed loop materializes the (K, K, m, m) cross-Gram and a
    (K, chunk, m) query tensor per chunk and re-traces for the ragged
    final chunk; the engine runs the residual recurrence in padded
    fixed-shape blocks.  Steady-state throughput is reported (both
    warmed), so the seed's extra re-jit is *not* counted against it;
    the parity row asserts both paths assign identical codes.  The
    pallas row runs interpret mode at a reduced size (correctness/call
    overhead tracking, not TPU latency).
    """
    from repro.core import codebooks as cb
    from repro.core.encode import icm_encode
    from repro.kernels.ref import icm_encode_gram

    if full:
        n = max(n, 1_000_000)
    key = jax.random.PRNGKey(seed)
    x = (jax.random.normal(key, (n, d))
         * jnp.linspace(0.3, 2.0, d)[None, :])
    C = cb.init_residual(jax.random.fold_in(key, 1), x[:4096], K, m,
                         iters=10)
    jax.block_until_ready(C)

    seed_fn = jax.jit(lambda e: icm_encode_gram(e, C, iters))

    def seed_loop():
        parts = []
        for s in range(0, n, chunk):
            parts.append(seed_fn(x[s: s + chunk]))   # ragged tail re-jits
        return jnp.concatenate(parts, axis=0)

    def engine_jnp():
        return icm_encode(x, C, iters, backend="jnp",
                          point_chunk=point_chunk)

    def timed(fn):
        out = host_copy(fn())                        # compile + warm, release
        ts = []
        for _ in range(repeats):
            t0 = time.time()
            jax.block_until_ready(fn())
            ts.append(time.time() - t0)
        # min-of-repeats: cpu-share throttled container (see ivf_bench)
        return out, min(ts)

    codes_seed, dt_seed = timed(seed_loop)
    codes_eng, dt_eng = timed(engine_jnp)
    parity = bool(jnp.all(codes_seed == codes_eng))
    rows = [
        dict(engine="seed_chunk_loop", n=n, encode_us_per_pt=round(
            dt_seed / n * 1e6, 3), pts_per_s=round(n / dt_seed)),
        dict(engine="tiled_jnp", n=n, encode_us_per_pt=round(
            dt_eng / n * 1e6, 3), pts_per_s=round(n / dt_eng),
            codes_match_seed=parity),
    ]
    # pallas interpret: reduced size, correctness/overhead tracking only
    x_s = x[:pallas_n]
    codes_p, dt_p = timed(lambda: icm_encode(x_s, C, iters,
                                             backend="pallas",
                                             block_n=block_n,
                                             interpret=True))
    rows.append(dict(engine="tiled_pallas_interpret", n=pallas_n,
                     encode_us_per_pt=round(dt_p / pallas_n * 1e6, 3),
                     pts_per_s=round(pallas_n / dt_p),
                     codes_match_jnp=bool(
                         jnp.all(codes_p == codes_eng[:pallas_n]))))

    out = dict(K=K, m=m, d=d, iters=iters, chunk=chunk,
               point_chunk=point_chunk, rows=rows,
               codes_parity_seed_vs_engine=parity,
               speedup_engine_vs_seed=round(dt_seed / dt_eng, 3))
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    for r in rows:
        print(f"encode,{r['engine']},n={r['n']},,,,,"
              f"{r['pts_per_s']},{r['encode_us_per_pt']}", flush=True)
    print(f"# encode engine-vs-seed speedup "
          f"{out['speedup_engine_vs_seed']}x (codes parity {parity}) "
          f"-> {out_path}", flush=True)
    return out


def train_bench(full: bool = False, *, out_path: str = "BENCH_train.json",
                n: int = 8192, epochs: int = 2, batch_size: int = 256,
                repeats: int = 3, seed: int = 0):
    """Scan-compiled epoch driver vs the seed per-batch host-dispatch
    loop on the joint ICQ trainer, written to ``out_path`` for cross-PR
    perf tracking (DESIGN.md §9).

    Both paths run the identical jitted step function; the delta is
    pure dispatch structure — one ``lax.scan`` + donated state per
    epoch vs one host round-trip (device_put of the indexed batch +
    dispatch + metric fetch) per batch.
    """
    from repro.configs.base import ICQConfig
    from repro.core import variance
    from repro.trainer import (compile_epoch, epoch_batches,
                               init_train_state, make_train_step)
    from repro.data import make_table1_dataset

    if full:
        n, epochs = max(n, 10_000), max(epochs, 8)
    xtr, ytr, _, _ = make_table1_dataset("dataset2")
    xtr, ytr = xtr[:n], ytr[:n]
    cfg = ICQConfig(d=16, num_codebooks=8, codebook_size=64, num_fast=2)
    key = jax.random.PRNGKey(seed)
    state = init_train_state(key, cfg, embed_kind="linear", d_raw=64,
                             mode="icq",
                             sample_batch=(xtr[:4096], ytr[:4096]))
    step = make_train_step(cfg, state["embed_apply"], state["opt"], "icq",
                           None, unit=state["unit"])
    nb = n // batch_size

    step_jit = jax.jit(step)

    def host_loop():
        params, opt_state = state["params"], state["opt_state"]
        rng = jax.random.PRNGKey(seed + 1)
        for ep in range(epochs):
            rng, k = jax.random.split(rng)
            perm = jax.random.permutation(k, n)
            var_state = variance.init_state(cfg.d)
            for b in range(nb):
                idx = perm[b * batch_size:(b + 1) * batch_size]
                params, opt_state, var_state, mets = step_jit(
                    params, opt_state, var_state, (xtr[idx], ytr[idx]))
        jax.block_until_ready(params)
        return params

    epoch_fn = compile_epoch(step, cfg.d, donate=False)

    def scan_loop():
        params, opt_state = state["params"], state["opt_state"]
        rng = jax.random.PRNGKey(seed + 1)
        for ep in range(epochs):
            rng, k = jax.random.split(rng)
            xb, yb = epoch_batches(k, xtr, ytr, batch_size)
            params, opt_state, var_state, mets = epoch_fn(params, opt_state,
                                                          xb, yb)
        jax.block_until_ready(params)
        return params

    # interleave the two drivers and take the median of paired ratios
    # (see lutq_bench: common-mode cpu-share interference cancels inside
    # each pair on this throttled container); per-row latencies report
    # min-of-repeats like the other benches
    host_loop()                                      # compile + warm
    scan_loop()
    ts_host, ts_scan = [], []
    for _ in range(3 * repeats):
        t0 = time.time()
        host_loop()
        ts_host.append(time.time() - t0)
        t0 = time.time()
        scan_loop()
        ts_scan.append(time.time() - t0)
    dt_host, dt_scan = min(ts_host), min(ts_scan)
    pair = sorted(h / s for h, s in zip(ts_host, ts_scan))
    speedup = pair[len(pair) // 2]
    steps_total = epochs * nb
    rows = [
        dict(driver="host_loop", n=n, epochs=epochs, batch=batch_size,
             us_per_step=round(dt_host / steps_total * 1e6, 1)),
        dict(driver="scan_epoch", n=n, epochs=epochs, batch=batch_size,
             us_per_step=round(dt_scan / steps_total * 1e6, 1)),
    ]
    out = dict(n=n, epochs=epochs, batch_size=batch_size,
               steps_per_epoch=nb, rows=rows,
               speedup_scan_vs_host=round(speedup, 3))
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    for r in rows:
        print(f"train,{r['driver']},n={r['n']},epochs={r['epochs']},"
              f"batch={r['batch']},,,,{r['us_per_step']}", flush=True)
    print(f"# train scan-vs-host speedup {out['speedup_scan_vs_host']}x "
          f"-> {out_path}", flush=True)
    return out


def faults_bench(full: bool = False, *, out_path: str = "BENCH_faults.json",
                 n: int = 20_000, nq: int = 32, batches: int = 12,
                 topk: int = 10, seed: int = 0):
    """Chaos target (docs/robustness.md): serve a deterministic fault
    schedule through the resilient engine and report degraded-rate and
    recall-under-faults, written to ``out_path``.

    A seeded ``FaultInjector`` raises inside the Pallas kernel stages
    (forcing the engine's pallas→jnp failover) while the batch stream
    cycles budgets — unbounded, a deadline far below the full path's
    warm time (forcing the ladder down), and crude-only.  Recall is
    measured per batch against a clean full-search engine on the same
    index: the run proves degradation stays *approximate* (recall
    reported), never wrong (no exceptions reach the caller).
    """
    from repro.api import build_ann_engine
    from repro.data.synthetic import make_synthetic_index
    from repro.resilience import FaultInjector, FaultSpec, SearchBudget

    if full:
        n, batches = max(n, 100_000), max(batches, 48)
    key = jax.random.PRNGKey(seed)
    codes, C, structure = make_synthetic_index(key, n, d=16, K=8, m=64,
                                               num_fast=2)
    clean = build_ann_engine(codes, C, structure, topk=topk, backend="jnp")
    inj = FaultInjector(seed=seed,
                        spec=FaultSpec(p_raise=0.3, targets=("kernels.",)))
    chaos = build_ann_engine(codes, C, structure, topk=topk,
                             backend="pallas", fault_injector=inj)
    budgets = (None,
               SearchBudget(deadline_ms=1e-3),     # forces the ladder down
               SearchBudget(allow_refine=False))   # crude floor outright
    recalls, degraded = [], 0
    with inj.installed():
        for i in range(batches):
            q = jax.random.normal(jax.random.fold_in(key, 100 + i),
                                  (nq, structure.xi.shape[0]))
            r = chaos.search(q, budget=budgets[i % len(budgets)])
            ref = clean.search(q)
            hit = np.mean([len(np.intersect1d(a, b)) / topk
                           for a, b in zip(np.asarray(r.indices),
                                           np.asarray(ref.indices))])
            recalls.append(float(hit))
            degraded += int(r.meta.degraded)
    out = {"n": n, "nq": nq, "batches": batches, "topk": topk,
           "seed": seed, "injector_counts": dict(inj.counts),
           "engine_stats": dict(chaos.stats),
           "degraded_rate": degraded / batches,
           "recall_under_faults": float(np.mean(recalls)),
           "recall_worst_batch": float(np.min(recalls))}
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"faults,chaos,n={n},batches={batches},"
          f"degraded_rate={out['degraded_rate']:.2f},"
          f"recall={out['recall_under_faults']:.3f},"
          f"failovers={chaos.stats.get('failovers', 0)},,", flush=True)
    print(f"# faults: degraded_rate {out['degraded_rate']:.2f}, "
          f"recall-under-faults {out['recall_under_faults']:.3f} "
          f"-> {out_path}", flush=True)
    return out


def pipeline_bench(full: bool = False, *,
                   out_path: str = "BENCH_pipeline.json",
                   n: int = 100_000, nq: int = 64, K: int = 8, m: int = 256,
                   topk: int = 50, d: int = 16, tile: int = 16,
                   repeats: int = 9, seed: int = 0):
    """Overlapped crude/refine pipeline (DESIGN.md §13) vs the jitted
    sequential two-step engine, end-to-end us/query at n points, written
    to ``out_path``.

    Both paths run the *same* index state; the sequential side is
    ``jax.jit(index.search)`` — exactly the program ``AnnEngine``
    serves — and the pipelined side is the same index rebuilt with
    ``pipeline="tiles"``, whose executor splits the query batch into
    tiles and dispatches crude(t+1) while refine(t) drains, donating
    the intermediate top-k carry between tiles.  Two operating points
    are measured: *refine-heavy* (``num_fast=2`` of K=8 — the refine
    stage recomputes 6 codebooks per survivor; eq. 2's threshold keeps
    the pass rate low, ~topk/n) and *crude-heavy* (``num_fast=K-2`` —
    the crude pass does nearly all the LUT work and refine touches 2).
    The headline per point is the median of paired ratios over
    interleaved samples (see lutq_bench: common-mode cpu-share
    interference cancels inside each pair); per-row latencies report
    min-of-repeats like the other benches.  Each point also asserts the
    two paths return bitwise-identical ids + distances — the pipeline
    is a pure scheduling change, never an accuracy knob.
    """
    from repro.data.synthetic import make_synthetic_index
    from repro.index import make_index

    if full:
        n, nq = max(n, 1_000_000), max(nq, 256)
    key = jax.random.PRNGKey(seed)
    queries = jax.random.normal(jax.random.fold_in(key, 2), (nq, d))

    rows, speedups = [], {}
    for point, num_fast in (("refine_heavy", 2), ("crude_heavy", K - 2)):
        codes, C, structure = make_synthetic_index(key, n, d=d, K=K, m=m,
                                                   num_fast=num_fast)
        idx = make_index("two-step", codes, C, structure, topk=topk,
                         backend="jnp")
        seq = jax.jit(lambda q, i=idx: i.search(q, topk))
        pipe = make_index("two-step", codes, C, structure, topk=topk,
                          backend="jnp", pipeline="tiles",
                          pipeline_tile=tile)
        res_s = host_copy(seq(queries))          # compile + warm both,
        res_p = host_copy(pipe.search(queries))  # buffers released
        bitwise = (bool(np.array_equal(res_s.indices, res_p.indices))
                   and bool(np.array_equal(res_s.distances,
                                           res_p.distances)))
        assert bitwise, f"pipeline diverged from sequential at {point}"
        ts_s, ts_p = [], []
        for _ in range(repeats):
            t0 = time.time()
            jax.block_until_ready(seq(queries).indices)
            ts_s.append(time.time() - t0)
            t0 = time.time()
            jax.block_until_ready(pipe.search(queries).indices)
            ts_p.append(time.time() - t0)
        pair_ratios = sorted(s / p for s, p in zip(ts_s, ts_p))
        speedups[point] = pair_ratios[len(pair_ratios) // 2]
        for engine, ts, res in (("sequential_jit", ts_s, res_s),
                                ("pipelined_tiles", ts_p, res_p)):
            rows.append(dict(point=point, engine=engine, n=n, nq=nq,
                             num_fast=num_fast,
                             search_us=round(min(ts) / nq * 1e6, 2),
                             avg_ops=round(float(res.avg_ops), 4),
                             pass_rate=round(float(res.pass_rate), 4),
                             bitwise_match=bitwise))

    out = dict(topk=topk, K=K, m=m, d=d, tile=tile, rows=rows,
               speedup_pipelined_refine_heavy=round(
                   speedups["refine_heavy"], 3),
               speedup_pipelined_crude_heavy=round(
                   speedups["crude_heavy"], 3))
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    for r in rows:
        print(f"pipeline,{r['point']},{r['engine']},n={r['n']},"
              f"nq={r['nq']},,{r['avg_ops']},{r['pass_rate']},,"
              f"{r['search_us']}", flush=True)
    print(f"# pipeline speedup refine-heavy "
          f"{out['speedup_pipelined_refine_heavy']}x / crude-heavy "
          f"{out['speedup_pipelined_crude_heavy']}x (tile={tile}, "
          f"bitwise ok) -> {out_path}", flush=True)
    return out


def config_overrides(cfg, target: str):
    """Kwargs for one engine-bench ``--only`` target from an api
    ``ICQConfig`` (repro.api, docs/api.md) — a checked-in config (e.g.
    ``benchmarks/configs/bench_small.json``) pins the geometry/engine
    options so a BENCH run is reproducible bit-for-bit from the repo."""
    t, e, i, s = cfg.train, cfg.encode, cfg.index, cfg.serve
    geom = dict(d=t.d, K=t.num_codebooks, m=t.codebook_size,
                num_fast=t.num_fast)
    table = {
        "search": dict(geom, topk=s.topk),
        "ivf": dict(geom, topk=s.topk, n_lists=i.n_lists,
                    **({"query_chunk": s.query_chunk}
                       if s.query_chunk is not None else {})),
        "lutq": dict(geom, topk=s.topk),
        "fastscan": dict(geom, topk=s.topk),
        "encode": dict(d=t.d, K=t.num_codebooks, m=t.codebook_size,
                       iters=e.icm_iters, chunk=e.chunk,
                       **({"point_chunk": e.point_chunk}
                          if e.point_chunk is not None else {})),
        "train": dict(epochs=t.epochs, batch_size=t.batch_size),
        # pipeline sweeps num_fast itself (its two operating points),
        # so only the remaining geometry comes from the config
        "pipeline": dict(d=t.d, K=t.num_codebooks, m=t.codebook_size,
                         topk=s.topk,
                         **({"tile": s.pipeline_tile}
                            if s.pipeline_tile is not None else {})),
        # serve sweeps the batch window itself; the config pins the
        # geometry and the coalescing tile (ServeConfig.batch_tile)
        "serve": dict(geom, topk=s.topk, tile=s.batch_tile),
    }
    return table.get(target)


CONFIG_TARGETS = ("search", "ivf", "lutq", "fastscan", "encode", "train",
                  "pipeline", "serve")

FIGURES = {
    "fig1": fig1_synthetic_pq.run,
    "fig2": fig2_synthetic_cq.run,
    "fig3": fig3_realworld_sq.run,
    "fig4": fig4_code_length.run,
    "fig5": fig5_pqn.run,
    "fig6": fig6_unseen.run,
    "beyond_ivf": beyond_ivf.run,
    "search": search_bench,
    "ivf": ivf_bench,
    "lutq": lutq_bench,
    "fastscan": fastscan_bench,
    "encode": encode_bench,
    "train": train_bench,
    "faults": faults_bench,
    "pipeline": pipeline_bench,
    "pareto": sweep.run,
    "serve": serve_load.run,
}


def kernel_micro():
    """Pallas-kernel microbenchmarks (interpret on CPU; wall time is NOT
    TPU-indicative — correctness + call-overhead tracking only)."""
    from repro.kernels import ops
    key = jax.random.PRNGKey(0)
    rows = []
    for name, fn, args in [
        ("adc_64k_x8", ops.adc,
         (jax.random.randint(key, (65536, 8), 0, 256),
          jax.random.normal(key, (8, 256)))),
        ("kmeans_16k_256", ops.kmeans_assign,
         (jax.random.normal(key, (16384, 64)),
          jax.random.normal(key, (256, 64)))),
        ("flash_4x512", ops.flash_attention,
         (jax.random.normal(key, (4, 512, 8, 64)),
          jax.random.normal(key, (4, 512, 2, 64)),
          jax.random.normal(key, (4, 512, 2, 64)))),
    ]:
        out = fn(*args)                      # compile
        jax.block_until_ready(out)
        t0 = time.time()
        for _ in range(3):
            jax.block_until_ready(fn(*args))
        us = (time.time() - t0) / 3 * 1e6
        print(f"kernel,{name},interpret,,,,,,{us:.0f}", flush=True)
        rows.append((name, us))
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes (hours on CPU)")
    ap.add_argument("--only", default=None,
                    help="run a single section; see docs/benchmarks.md "
                         f"(one of: {', '.join(FIGURES)})")
    ap.add_argument("--config", default=None,
                    help="repro.api ICQConfig JSON pinning the bench "
                         "geometry/engine options (engine targets only: "
                         f"{', '.join(CONFIG_TARGETS)}); e.g. the "
                         "checked-in benchmarks/configs/bench_small.json")
    ap.add_argument("--seed", type=int, default=0,
                    help="PRNG seed threaded through every target's data "
                         "generation; same seed => identical "
                         "recall/avg_ops numbers across runs")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.only is not None and args.only not in FIGURES:
        # a typo'd name used to silently run *nothing*; fail loudly
        ap.error(f"unknown --only target {args.only!r}; valid targets: "
                 f"{', '.join(sorted(FIGURES))}")
    overrides = {}
    if args.config is not None:
        if args.only not in CONFIG_TARGETS:
            ap.error(f"--config drives the engine targets "
                     f"({', '.join(CONFIG_TARGETS)}); pass --only "
                     "with one of them")
        from repro.api import ICQConfig
        cfg = ICQConfig.load(args.config)
        overrides = config_overrides(cfg, args.only)
        print(f"# config {args.config} (hash {cfg.config_hash()[:12]}) "
              f"-> {overrides}", flush=True)

    header()
    t0 = time.time()
    for name, run_fn in FIGURES.items():
        if args.only and name != args.only:
            continue
        run_fn(full=args.full, seed=args.seed,
               **(overrides if name == args.only else {}))
    if not args.only:
        kernel_micro()
    print(f"# total {time.time() - t0:.0f}s", flush=True)


if __name__ == "__main__":
    main()
