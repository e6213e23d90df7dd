"""The per-layer metrics that read the program's own spans
(``bench/program_spans.py``): their arithmetic on a synthetic trace,
checked against hand counts and a brute-force count on a 1-ns grid, and
one traced run of a tiny cell of each kind on the CPU."""
import numpy as np
import pytest
from tinycell import run_tiny

from bench import cells, program_spans, tracing

T0, T1 = 1_000, 101_000                 # the bench.window, ns


def _trace(main=(), worker=(), other=(), ops=()):
    return {"planes": [
        {"name": "/host:CPU", "lines": [
            {"name": "python",
             "events": [["bench.window", T0, T1 - T0]] + list(main)},
            {"name": "repro-serve-loop", "events": list(worker)},
            {"name": "other", "events": list(other)}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": list(ops)}]}]}


def _read(monkeypatch, name, trace):
    monkeypatch.setattr(program_spans, "_trace_dict", lambda: trace)
    return cells.metric_reader(name)({"trace": tracing.Reduced(trace)})


def _ev(name, s, e):
    return [name, s, e - s]


def test_host_ms_subtracts_the_union_of_waits_and_clips_at_the_window(
        monkeypatch):
    main = [
        # starts before the window: clipped to [T0, 20000); two waits
        # that overlap, union [5000, 15000)
        _ev("repro.search", 0, 20_000),
        _ev("repro.engine.wait", 5_000, 12_000),
        _ev("repro.engine.wait", 10_000, 15_000),
        # one wait of 16000
        _ev("repro.search", 30_000, 50_000),
        _ev("repro.engine.wait", 32_000, 48_000),
        # runs past the window: clipped to [95000, T1), its wait too
        _ev("repro.search", 95_000, 110_000),
        _ev("repro.engine.wait", 96_000, 108_000),
        # outside the window: not counted
        _ev("repro.search", 200_000, 210_000),
    ]
    # a wait on another thread does not belong to these calls
    other = [_ev("repro.engine.wait", 40_000, 45_000)]
    got = _read(monkeypatch, "engine.host_ms.batch", _trace(main,
                                                           other=other))
    want = (9_000 + 4_000 + 1_000) / 3 / 1e6
    assert got == pytest.approx(want)


def test_flush_host_ms_reads_the_worker_spans(monkeypatch):
    worker = [
        _ev("repro.serve.flush", 10_000, 40_000),
        _ev("repro.engine.search", 12_000, 38_000),
        _ev("repro.engine.wait", 15_000, 30_000),
        _ev("repro.engine.wait", 29_000, 36_000),      # overlaps the first
        _ev("repro.serve.flush", 60_000, 70_000),      # no device wait
    ]
    got = _read(monkeypatch, "serve.flush_host_ms_mean",
                _trace(worker=worker))
    assert got == pytest.approx((30_000 - 21_000 + 10_000) / 2 / 1e6)


def test_submit_p95_is_the_percentile_of_clipped_durations(monkeypatch):
    durs = [700, 900, 1_100, 1_300, 2_000, 9_000]
    main, t = [], 2_000
    for d in durs:
        main.append(_ev("repro.serve.submit", t, t + d))
        t += 10_000
    main.append(_ev("repro.serve.submit", T1 - 500, T1 + 4_000))
    got = _read(monkeypatch, "serve.submit_ms_p95", _trace(main))
    assert got == pytest.approx(np.percentile(durs + [500], 95) / 1e6)


def test_idle_in_flush_share_matches_brute_force(monkeypatch):
    ops = [_ev("crude_topk_pallas.1", 5_000, 25_000),
           _ev("refine_topk_pallas.1", 24_000, 40_000),
           _ev("crude_topk_pallas.1", 62_000, 80_000),
           _ev("fusion.3", 95_000, 104_000)]
    worker = [_ev("repro.serve.idle", 0, 3_000),
              _ev("repro.serve.flush", 3_000, 45_000),
              _ev("repro.serve.idle", 45_000, 58_000),
              _ev("repro.serve.flush", 58_000, 83_000),
              _ev("repro.serve.flush", 90_000, 99_000)]
    # a flush span on another line is not the worker's
    other = [_ev("repro.serve.flush", 40_000, 60_000)]
    got = _read(monkeypatch, "device.idle_in_flush_share.serve",
                _trace(worker=worker, other=other, ops=ops))
    t = np.arange(T0, T1)
    busy = np.zeros(t.shape, bool)
    for _, s, d in ops:
        busy |= (t >= s) & (t < s + d)
    flush = np.zeros(t.shape, bool)
    for n, s, d in worker:
        if n == "repro.serve.flush":
            flush |= (t >= s) & (t < s + d)
    want = 100.0 * (~busy & flush).sum() / (~busy).sum()
    assert got == pytest.approx(want)
    assert 0 < got < 100


@pytest.mark.parametrize("name", ["engine.host_ms.batch",
                                  "serve.flush_host_ms_mean",
                                  "serve.submit_ms_p95",
                                  "device.idle_in_flush_share.serve"])
def test_a_program_without_spans_reads_nothing(monkeypatch, name):
    """The parent of this change writes no ``repro.`` span: every reader
    returns None, and none raises."""
    trace = _trace(ops=[_ev("crude_topk_pallas.1", 5_000, 25_000)])
    assert _read(monkeypatch, name, trace) is None
    assert cells.metric_reader(name)({}) is None


@pytest.mark.parametrize("cell,names", [
    ("sift1m-twostep.batch64", ["engine.host_ms.batch"]),
    ("sift1m-twostep.poisson1", ["serve.flush_host_ms_mean",
                                 "serve.submit_ms_p95"]),
])
def test_a_traced_tiny_run_reads_each_span_metric(cell, names):
    """The CPU trace holds no TPU plane, so the device share is left out
    there (a device metric is never read off the CPU); the host metrics
    read the program's spans."""
    out = run_tiny(cell, trace=True, seconds=0.5)
    assert out["correct"], out["checks"]
    for n in names:
        assert out["metrics"][n]["value"] > 0, n
    assert "device.idle_in_flush_share.serve" not in out["metrics"]
