"""The async serving engine (docs/serving.md): coalescer state machine
(pure, fake-clock driven), serving-loop bitwise parity vs direct calls
across all three index kinds, multi-tenant routing + spec validation,
queue/batching metadata, degraded-not-broken under injected faults, and
seeded load-generator determinism.
"""
import time
from concurrent.futures import Future

import jax
import numpy as np
import pytest

from repro.api import build_ann_engine, icq_session, ICQConfig
from repro.core import codebooks as cb
from repro.data.synthetic import make_synthetic_index
from repro.resilience import FaultInjector, FaultSpec, ResultMeta, \
    SearchBudget
from repro.serve import (Coalescer, PendingRequest, ServeError, ServingLoop,
                         Tenant, make_workload, parse_tenant_specs,
                         poisson_arrivals, run_open_loop, summarize)

D, TOPK = 16, 10


def _req(nq, t=0.0, tenant="t"):
    q = np.arange(nq * D, dtype=np.float32).reshape(nq, D)
    return PendingRequest(tenant, q, None, None, t, Future())


# --------------------------------------------------------------- engines --
@pytest.fixture(scope="module")
def engines():
    """One small engine per index kind (jnp backend)."""
    key = jax.random.PRNGKey(0)
    codes, C, structure = make_synthetic_index(key, 2000, d=D, K=8, m=32,
                                               num_fast=2)
    out = {
        "flat": build_ann_engine(codes, C, structure, topk=TOPK,
                                 backend="jnp", index="flat"),
        "two-step": build_ann_engine(codes, C, structure, topk=TOPK,
                                     backend="jnp"),
        "ivf": build_ann_engine(codes, C, structure, topk=TOPK,
                                backend="jnp", index="ivf",
                                emb_db=cb.decode(C, codes), n_lists=16,
                                n_probe=4, key=jax.random.fold_in(key, 1)),
    }
    return out


# ------------------------------------------------- coalescer state machine --
class TestCoalescer:
    def test_flush_on_full_tile_fires_immediately(self):
        c = Coalescer(tile=4, window_s=10.0)   # window can't be the trigger
        assert c.submit(_req(3), now=0.0) == []
        flushes = c.submit(_req(1), now=0.1)
        assert len(flushes) == 1
        assert flushes[0].reason == "full"
        assert flushes[0].rows == flushes[0].tile == 4
        assert c.pending_rows == 0

    def test_flush_on_window_expiry(self):
        c = Coalescer(tile=8, window_s=0.5)
        c.submit(_req(3), now=1.0)
        assert c.next_deadline() == pytest.approx(1.5)
        assert c.poll(now=1.49) == []          # window not yet expired
        flushes = c.poll(now=1.5)
        assert len(flushes) == 1
        assert flushes[0].reason == "window"
        assert flushes[0].rows == 3 and flushes[0].tile == 8
        assert flushes[0].fill == pytest.approx(3 / 8)
        assert c.poll(now=2.0) == [] and c.next_deadline() is None

    def test_oversize_burst_splits_across_tiles(self):
        c = Coalescer(tile=4, window_s=1.0)
        req = _req(10)
        flushes = c.submit(req, now=0.0)
        assert [f.reason for f in flushes] == ["full", "full"]
        assert [f.rows for f in flushes] == [4, 4]
        # the remainder waits for more rows or the window
        assert c.pending_rows == 2
        spans = [(s.req_start, s.rows) for f in flushes for s in f.slices]
        assert spans == [(0, 4), (4, 4)]
        tail = c.flush_all()
        assert [f.rows for f in tail] == [2]
        assert tail[0].slices[0].req_start == 8

    def test_fifo_packing_and_row_routing(self):
        c = Coalescer(tile=6, window_s=1.0)
        a, b, d = _req(2, t=0.0), _req(3, t=0.1), _req(4, t=0.2)
        c.submit(a, now=0.0)
        c.submit(b, now=0.1)
        flushes = c.submit(d, now=0.2)         # 9 rows pending -> one tile
        assert len(flushes) == 1
        f = flushes[0]
        # FIFO: a's 2 rows, b's 3, then d's first row fills the tile
        assert [(s.request.rid, s.req_start, s.batch_start, s.rows)
                for s in f.slices] == [
            (a.rid, 0, 0, 2), (b.rid, 0, 2, 3), (d.rid, 0, 5, 1)]
        # the concatenated tile rows are exactly the requests' rows
        np.testing.assert_array_equal(
            f.queries(),
            np.concatenate([a.queries, b.queries, d.queries[:1]]))
        # window re-arms from the split survivor's submit time
        assert c.next_deadline() == pytest.approx(0.2 + 1.0)

    def test_deliver_and_assemble_reorders_split_parts(self):
        req = _req(5)
        ids_a = np.arange(10).reshape(2, 5)
        ids_b = np.arange(15).reshape(3, 5) + 100
        # parts can complete out of order; assemble sorts by req_start
        assert not req.deliver(2, ids_b, ids_b * 0.5, "resB", fill=1.0)
        assert req.deliver(0, ids_a, ids_a * 0.5, "resA", fill=0.5)
        ids, dists, last, fill = req.assemble()
        np.testing.assert_array_equal(ids, np.concatenate([ids_a, ids_b]))
        assert last == "resB"                  # last part by request row
        assert fill == pytest.approx((2 * 0.5 + 3 * 1.0) / 5)

    def test_flush_all_drains_everything(self):
        c = Coalescer(tile=4, window_s=9.0)
        c.submit(_req(3), now=0.0)
        c.submit(_req(3), now=0.0)             # -> one full flush emitted
        drained = c.flush_all()
        assert sum(f.rows for f in drained) == 2
        assert all(f.reason == "drain" for f in drained)
        assert c.pending_rows == 0 and c.flush_all() == []

    def test_invalid_knobs_rejected(self):
        with pytest.raises(ServeError, match="tile"):
            Coalescer(tile=0, window_s=1.0)
        with pytest.raises(ServeError, match="window"):
            Coalescer(tile=4, window_s=-0.1)


# ------------------------------------------------------- loop bitwise parity --
class TestServingLoopParity:
    @pytest.mark.parametrize("kind", ["flat", "two-step", "ivf"])
    def test_coalesced_bitwise_identical_to_direct(self, engines, kind):
        """The hard invariant: scheduling never changes math — ids AND
        distances of a coalesced response equal a direct search on the
        same rows, for every index kind, across coalesced/split/padded
        flushes."""
        eng = engines[kind]
        rng = np.random.default_rng(3)
        reqs = [rng.standard_normal((nq, D)).astype(np.float32)
                for nq in (1, 2, 4, 1, 5, 3)]  # 5 > tile: split path
        with ServingLoop(Tenant(name="t", engine=eng), window_ms=1.0,
                         tile=4) as loop:
            loop.warm()
            futs = [loop.submit(q) for q in reqs]
            results = [f.result(timeout=60) for f in futs]
        for q, res in zip(reqs, results):
            ref = eng.search(q)
            np.testing.assert_array_equal(np.asarray(res.indices),
                                          np.asarray(ref.indices))
            np.testing.assert_array_equal(np.asarray(res.distances),
                                          np.asarray(ref.distances))

    def test_searcher_tenant_parity_and_meta(self, rng, key):
        """A Searcher-backed tenant (embed model in front) serves
        bitwise what searcher.search returns, and only the loop's
        results carry queue_ms/batch_fill."""
        X = rng.standard_normal((256, 32)).astype(np.float32)
        sess = icq_session(ICQConfig().with_overrides(
            {"train.d": 16, "train.num_codebooks": 4,
             "train.codebook_size": 16, "train.epochs": 1}))
        sess.fit(X, key=key)
        searcher = sess.index(
            rng.standard_normal((400, 32)).astype(np.float32))
        q = rng.standard_normal((3, 32)).astype(np.float32)
        with ServingLoop(Tenant.from_searcher("s", searcher),
                         window_ms=1.0, tile=4) as loop:
            res = loop.search(q, k=5)
        ref = searcher.search(q, 5)
        np.testing.assert_array_equal(np.asarray(res.indices),
                                      np.asarray(ref.indices))
        np.testing.assert_array_equal(np.asarray(res.distances),
                                      np.asarray(ref.distances))
        # loop results carry the serving metadata; direct ones don't
        assert res.meta.queue_ms is not None and res.meta.queue_ms >= 0
        assert res.meta.batch_fill == pytest.approx(3 / 4)
        assert ref.meta.queue_ms is None and ref.meta.batch_fill is None

    def test_offline_meta_defaults_are_none(self):
        m = ResultMeta()
        assert m.queue_ms is None and m.batch_fill is None


# --------------------------------------------------------- loop lifecycle --
class TestServingLoopLifecycle:
    def test_close_drains_pending_requests(self, engines):
        """Clean shutdown: requests still queued (window not yet
        expired) are served, not dropped."""
        loop = ServingLoop(Tenant(name="t", engine=engines["two-step"]),
                           window_ms=10_000.0, tile=32).start()
        q = np.zeros((2, D), np.float32)
        fut = loop.submit(q)                   # far below the tile; only
        loop.close()                           # the drain can flush it
        res = fut.result(timeout=5)
        assert np.asarray(res.indices).shape == (2, TOPK)
        with pytest.raises(ServeError, match="closed"):
            loop.submit(q)
        loop.close()                           # idempotent

    def test_never_started_close_serves_inline(self, engines):
        loop = ServingLoop(Tenant(name="t", engine=engines["two-step"]),
                           window_ms=10_000.0, tile=8)
        fut = loop.submit(np.zeros((1, D), np.float32))
        loop.close()
        assert np.asarray(fut.result(timeout=5).indices).shape == (1, TOPK)

    def test_max_queue_backpressure(self, engines):
        loop = ServingLoop(Tenant(name="t", engine=engines["two-step"]),
                           window_ms=10_000.0, tile=32, max_queue=4)
        for _ in range(4):
            loop.submit(np.zeros((1, D), np.float32))
        with pytest.raises(ServeError, match="queue full"):
            loop.submit(np.zeros((1, D), np.float32))
        loop.close()

    def test_submit_validation(self, engines):
        t1 = Tenant(name="a", engine=engines["flat"])
        t2 = Tenant(name="b", engine=engines["two-step"])
        with ServingLoop([t1, t2], window_ms=1.0, tile=4) as loop:
            with pytest.raises(ServeError, match="pass "):
                loop.submit(np.zeros((1, D), np.float32))  # ambiguous
            with pytest.raises(ServeError, match="unknown tenant"):
                loop.submit(np.zeros((1, D), np.float32), tenant="zzz")
            with pytest.raises(ServeError, match="d="):
                loop.submit(np.zeros((1, D + 1), np.float32), tenant="a")
            with pytest.raises(ServeError, match="shape"):
                loop.submit(np.zeros((1, 1, D), np.float32), tenant="a")


# ------------------------------------------------------------ multi-tenant --
class TestTenants:
    def test_parse_tenant_specs_conflicts(self, tmp_path):
        a, b = tmp_path / "a", tmp_path / "b"
        a.mkdir(), b.mkdir()
        assert parse_tenant_specs([f"x={a}", f"y={b}"]) == [
            ("x", str(a)), ("y", str(b))]
        with pytest.raises(ServeError, match="NAME=ARTIFACTS_DIR"):
            parse_tenant_specs(["noequals"])
        with pytest.raises(ServeError, match="duplicate tenant name"):
            parse_tenant_specs([f"x={a}", f"x={b}"])
        with pytest.raises(ServeError, match="both point at"):
            # same dir through a symlink-free alias still collides
            parse_tenant_specs([f"x={a}", f"y={tmp_path}/./a"])

    def test_tenant_name_validation(self, engines):
        with pytest.raises(ServeError, match="name"):
            Tenant(name="", engine=engines["flat"])
        with pytest.raises(ServeError, match="name"):
            Tenant(name="a=b", engine=engines["flat"])
        with pytest.raises(ServeError, match="duplicate"):
            ServingLoop([Tenant(name="a", engine=engines["flat"]),
                         Tenant(name="a", engine=engines["two-step"])])

    def test_per_tenant_routing_is_isolated(self, engines):
        """Requests coalesce per lane: each tenant's rows only ever hit
        its own engine."""
        t1 = Tenant(name="flat", engine=engines["flat"])
        t2 = Tenant(name="ivf", engine=engines["ivf"])
        rng = np.random.default_rng(7)
        q = rng.standard_normal((2, D)).astype(np.float32)
        with ServingLoop([t1, t2], window_ms=1.0, tile=4) as loop:
            r1 = loop.search(q, tenant="flat")
            r2 = loop.search(q, tenant="ivf")
        np.testing.assert_array_equal(
            np.asarray(r1.indices),
            np.asarray(engines["flat"].search(q).indices))
        np.testing.assert_array_equal(
            np.asarray(r2.indices),
            np.asarray(engines["ivf"].search(q).indices))


# ----------------------------------------------------- degraded, not broken --
class TestDegradedServing:
    def test_fault_delay_under_deadline_degrades_without_errors(self):
        """Injected stage delays + a tight per-tenant deadline: the
        ladder serves degraded responses; no request errors out."""
        key = jax.random.PRNGKey(1)
        codes, C, structure = make_synthetic_index(key, 2000, d=D, K=8,
                                                   m=32, num_fast=2)
        inj = FaultInjector(seed=0, spec=FaultSpec(
            p_delay=0.9, delay_ms=15.0, targets=("engine.search",)))
        eng = build_ann_engine(codes, C, structure, topk=TOPK,
                               backend="jnp", fault_injector=inj)
        tenant = Tenant(name="t", engine=eng,
                        budget=SearchBudget(deadline_ms=1.0))
        rng = np.random.default_rng(5)
        with inj.installed():
            with ServingLoop(tenant, window_ms=0.5, tile=4) as loop:
                futs = [loop.submit(
                    rng.standard_normal((1, D)).astype(np.float32))
                    for _ in range(12)]
                results = [f.result(timeout=60) for f in futs]
        assert len(results) == 12              # nothing raised
        assert all(r.meta is not None for r in results)
        assert any(r.meta.degraded for r in results)
        # the tenant default budget reached the engine: deadlines stamped
        assert all(r.meta.deadline_ms == 1.0 for r in results)


# ---------------------------------------------------------------- loadgen --
class TestLoadgen:
    def test_poisson_arrivals_seeded_and_bounded(self):
        a = poisson_arrivals(100.0, 2.0, rng=np.random.default_rng(0))
        b = poisson_arrivals(100.0, 2.0, rng=np.random.default_rng(0))
        np.testing.assert_array_equal(a, b)
        assert (a >= 0).all() and (a < 2.0).all()
        assert (np.diff(a) >= 0).all()
        # ~rate*duration arrivals, very loose tolerance
        assert 100 < len(a) < 320
        c = poisson_arrivals(100.0, 2.0, rng=np.random.default_rng(9))
        assert not np.array_equal(a, c)
        with pytest.raises(ValueError, match="rate_hz"):
            poisson_arrivals(0.0, 1.0, rng=np.random.default_rng(0))

    def test_make_workload_same_seed_identical(self):
        pools = {"b": np.ones((8, D), np.float32) * 2,
                 "a": np.ones((8, D), np.float32)}
        w1 = make_workload(pools, 80.0, 1.0, rng=np.random.default_rng(4))
        w2 = make_workload(pools, 80.0, 1.0, rng=np.random.default_rng(4))
        assert len(w1) == len(w2) > 0
        for s1, s2 in zip(w1, w2):
            assert s1.t_arrival == s2.t_arrival
            assert s1.tenant == s2.tenant
            np.testing.assert_array_equal(s1.queries, s2.queries)
        assert {s.tenant for s in w1} <= {"a", "b"}

    def test_open_loop_records_and_summary(self, engines):
        pools = {"t": np.asarray(
            np.random.default_rng(1).standard_normal((8, D)), np.float32)}
        work = make_workload(pools, 200.0, 0.2,
                             rng=np.random.default_rng(2))
        with ServingLoop(Tenant(name="t", engine=engines["two-step"]),
                         window_ms=1.0, tile=4) as loop:
            loop.warm()
            t0 = time.time()
            recs = run_open_loop(loop, work)
            wall = time.time() - t0
        s = summarize(recs, wall_s=wall)
        assert s["requests"] == len(work)
        assert np.isfinite(s["p50_ms"]) and np.isfinite(s["p99_ms"])
        assert s["p50_ms"] <= s["p99_ms"]
        assert s["qps"] > 0 and s["rows_per_s"] >= s["qps"]
        assert 0 < s["mean_batch_fill"] <= 1.0
        assert s["mean_queue_ms"] >= 0


class _GatedEngine:
    """A stand-in engine that serves a flush only when the test releases
    it, and reports which requests each flush carried (a query row's
    first coordinate is its request number; pad rows are zero)."""

    def __init__(self, d: int):
        import queue
        import types
        self.index = types.SimpleNamespace(C=np.zeros((1, 1, d)))
        self.query_tile = None
        self.started = queue.Queue()
        self.release = queue.Queue()

    def search(self, q, k=None, *, budget=None):
        from repro.index.base import SearchResult
        q = np.asarray(q)
        self.started.put(sorted(int(v) for v in q[:, 0] if v > 0))
        self.release.get(timeout=30)
        zeros = np.zeros((q.shape[0], 1))
        return SearchResult(zeros.astype(np.int32),
                            zeros.astype(np.float32), 0.0, 0.0)


def test_a_backlog_left_by_a_stall_drains():
    """Rows that arrive while a flush runs join the very next flush,
    also after a stall left a full tile waiting.  A window batch formed
    while another flush already waits would stay one flush stale for
    good, adding a flush to every later request's wait."""
    eng = _GatedEngine(d=2)
    count = iter(range(1, 100))

    def send(loop):
        i = next(count)
        loop.submit(np.array([[i, 0.0]], np.float32))
        return i

    with ServingLoop(Tenant(name="t", engine=eng), tile=2,
                     window_ms=0.0) as loop:
        first = send(loop)
        assert eng.started.get(timeout=30) == [first]
        # the stall: while flush 0 runs, a full tile and one row queue up
        stalled = [send(loop) for _ in range(3)]
        eng.release.put(None)
        assert eng.started.get(timeout=30) == stalled[:2]
        for _ in range(4):
            new = send(loop)
            eng.release.put(None)
            assert new in eng.started.get(timeout=30)
        for _ in range(3):
            eng.release.put(None)


def test_a_saturating_lane_leaves_other_lanes_their_window():
    """While one lane queues a full tile during every flush, another
    lane's window batch still forms and waits behind at most one flush:
    a lane skips forming a window batch only while one of its own
    flushes waits."""
    eng = _GatedEngine(d=2)
    count = iter(range(1, 100))

    def send(loop, k=None):
        i = next(count)
        loop.submit(np.array([[i, 0.0]], np.float32), k=k)
        return i

    with ServingLoop(Tenant(name="t", engine=eng), tile=2,
                     window_ms=0.0) as loop:
        first = send(loop)
        assert eng.started.get(timeout=30) == [first]
        # while flush 0 runs: lane A (k=None) queues a full tile and
        # lane B (k=5) one row whose window has passed
        busy = [send(loop), send(loop)]
        other = send(loop, k=5)
        eng.release.put(None)
        assert eng.started.get(timeout=30) == busy
        # lane A queues another full tile during that flush, ahead of
        # which lane B's window batch was already formed
        send(loop), send(loop)
        eng.release.put(None)
        assert eng.started.get(timeout=30) == [other]
        for _ in range(3):
            eng.release.put(None)
