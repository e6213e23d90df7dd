#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest Poisson rate the
program sustains without a growing backlog.  One process builds the
cell's index once, then offers each rate for ``--seconds`` through its
traffic kind's driver (a fresh ``ServingLoop`` each) and reports completed requests per second, the
latency median and 95th percentile, and the backlog trend (median
latency of the last fifth of requests over the first fifth).

    python3 bench/sweep.py --workload sift1m-twostep.poisson1 --seed 1 \
        --rates 500,1000,1500,2000 --seconds 5

Run once on the chip when the cell is defined; the chosen rate goes
into the traffic file as a number.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import numpy as np

    from bench import cells, loadgen, run

    run.require_chip(1)
    run.enable_compile_cache()
    cell = cells.find_cell(args.workload)
    cfg, mix = cell["config"], cell["traffic"]
    learn, base, pool, _ = run.make_data(cfg)
    searcher = run.build(cfg, learn, base)
    kind = cells.traffic_kind(mix["kind"])
    for rate in [float(r) for r in args.rates.split(",")]:
        at_rate = dict(mix, rate_hz=rate)
        state = kind.warm(searcher, pool, at_rate)
        recs = kind.window(state, pool, at_rate, args.seconds, args.seed,
                           loadgen.no_span)["layer"]["records"]
        kind.close(state)
        lat = loadgen.latencies_ms(recs)
        done = [r for r in recs if r["done"] is not None]
        span = max(r["done"] for r in done) - min(r["due"] for r in done)
        fifth = max(len(lat) // 5, 1)
        print(json.dumps({
            "rate_hz": rate, "offered": len(recs), "completed": len(done),
            "completed_per_s": len(done) / span,
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "backlog_trend": float(np.median(lat[-fifth:])
                                   / np.median(lat[:fifth])),
            "late_ms_p95": float(np.percentile(loadgen.late_ms(recs), 95))}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
