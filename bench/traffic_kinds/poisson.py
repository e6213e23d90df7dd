"""Open loop: Poisson arrivals of ``rows``-row requests at ``rate_hz``
through ``ServingLoop.submit``, each timed from when it was due to when
its answer is on the host (``loadgen.run_open``).  The gaps come from
the mix's ``gap_seed`` and the rows asked from a fixed walk of the pool;
a run's seed permutes both, so every run offers the same requests in
another order.

End to end: ``latency_p50_ms`` and ``latency_p95_ms`` over every request
of the window.  A request refused, raised, or never answered counts as
lost.
"""
from __future__ import annotations

import numpy as np

from bench import loadgen

WARM_REQUESTS = 64             # requests through the loop before the window
SETTLE_S = 60.0                # wait for answers past the window's close


def warm(searcher, pool, mix: dict) -> dict:
    """A serving loop over ``searcher``, its tile compiled and warmed."""
    from repro.serve import ServingLoop, Tenant

    loop = ServingLoop(Tenant.from_searcher("bench", searcher))
    loop.start()
    loop.warm()
    r = int(mix["rows"])
    for i in range(WARM_REQUESTS):
        res = loop.submit(pool[i:i + r]).result(timeout=600)
    return {"loop": loop, "backend": res.meta.backend}


def offer(loop, pool, mix: dict, seconds: float, seed: int,
          span=loadgen.no_span) -> list:
    """The window's requests through ``loop``, each record with the pool
    ``rows`` it asked, its times and its result or error."""
    r = int(mix["rows"])
    due = loadgen.poisson_due_times(float(mix["rate_hz"]), seconds,
                                    gap_seed=int(mix["gap_seed"]),
                                    order_seed=seed)
    n_rows = len(due) * r
    walk = np.random.default_rng(int(mix["gap_seed"])).permutation(
        pool.shape[0])
    rows = walk[np.arange(n_rows) % pool.shape[0]]
    rng = np.random.default_rng([seed % (1 << 63), 1])
    picks = rng.permutation(rows).reshape(len(due), r)
    with span("bench.window"):
        records = loadgen.run_open(lambda i: loop.submit(pool[picks[i]]),
                                   due, span=span)
        with span("bench.settle"):
            loadgen.settle(records, timeout_s=seconds + SETTLE_S)
    for rec in records:
        rec["rows"] = picks[rec["i"]]
    return records


def window(state: dict, pool, mix: dict, seconds: float, seed: int,
           span) -> dict:
    records = offer(state["loop"], pool, mix, seconds, seed, span)
    done = [rec for rec in records if rec["error"] is None
            and rec.get("result") is not None and rec["done"] is not None]
    lat = loadgen.latencies_ms(done)
    answers = [{"rows": rec["rows"],
                "ids": np.asarray(rec["result"].indices),
                "dists": np.asarray(rec["result"].distances),
                "backend": rec["result"].meta.backend} for rec in done]
    r = int(mix["rows"])
    return {"answers": answers, "attempted": len(records) * r,
            "lost": (len(records) - len(done)) * r,
            "metrics": {"latency_p95_ms": float(np.percentile(lat, 95)),
                        "latency_p50_ms": float(np.percentile(lat, 50))},
            "layer": {"records": records, "done": done}}


def close(state: dict) -> None:
    loop = state.pop("loop", None)
    if loop is not None:
        loop.close()
