"""Closed loop: one client sends ``batch`` pool rows at a time through
``Searcher.search``, walking the pool in an order drawn from the run's
seed and wrapping, and copies each answer's ids and distances to the
host before the next call.

End to end: ``qps``, the queries answered in the window over the window
(the first call's start to the last call's end).
"""
from __future__ import annotations

import numpy as np

from bench import loadgen


def warm(searcher, pool, mix: dict) -> dict:
    """Compile and warm the one shape the window sends."""
    b = int(mix["batch"])
    for _ in range(2):
        res = searcher.search(pool[:b])
        np.asarray(res.indices)
    return {"searcher": searcher, "backend": res.meta.backend}


def window(state: dict, pool, mix: dict, seconds: float, seed: int,
           span) -> dict:
    searcher = state["searcher"]
    b = int(mix["batch"])
    n_pool = pool.shape[0]
    order = np.random.default_rng([seed % (1 << 63), 3]).permutation(n_pool)

    def call(i):
        rows = order[(i * b + np.arange(b)) % n_pool]
        with span("bench.search"):
            res = searcher.search(pool[rows])
        with span("bench.host_copy"):
            ids = np.asarray(res.indices)
            dists = np.asarray(res.distances)
        return {"rows": rows, "ids": ids, "dists": dists, "meta": res.meta,
                "backend": res.meta.backend, "pass_rate": res.pass_rate}

    with span("bench.window"):
        calls, elapsed = loadgen.run_closed(call, seconds)
    attempted = len(calls) * b
    return {"answers": calls, "attempted": attempted, "lost": 0,
            "metrics": {"qps": attempted / elapsed},
            "layer": {"calls": calls, "batch": b}}


def close(state: dict) -> None:
    state.clear()
