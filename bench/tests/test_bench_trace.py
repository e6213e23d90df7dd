"""The trace reduction, on a trace recorded on one TPU v5e during the
two-step batch cell (80 ms of its window: three nq=64 batches) and
trimmed to the device's op line and the benchmark's spans.  Every number
is checked against a brute-force count on a 1-microsecond grid."""
import json
import os

import numpy as np
import pytest
from tinycell import ROOT

from bench import cells, tracing

TRACE = os.path.join(ROOT, "bench", "tests", "data",
                     "trace_v5e_twostep.json")
KERNELS = (r"^(ivf_)?(crude|refine)_topk_pallas(\.\d+)?$",)


@pytest.fixture(scope="module")
def trace():
    with open(TRACE) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def grid(trace):
    """Busy microseconds of the window, brute force."""
    red = tracing.Reduced(trace)
    t0 = red.t0
    n = (red.t1 - red.t0) // 1000
    busy = np.zeros(n, bool)
    kern = 0
    for plane in trace["planes"]:
        if plane["name"].startswith("/device:TPU"):
            for name, s, d in plane["lines"][0]["events"]:
                a = max(0, (s - t0) // 1000)
                b = min(n, (s + d - t0) // 1000)
                if b > a:
                    busy[a:b] = True
                    if "topk_pallas" in name:
                        kern += (b - a)
    return red, busy, kern


def test_window_is_the_bench_window_span(grid):
    red, busy, _ = grid
    assert red.window_s == pytest.approx(0.080)
    assert red.devices == ["/device:TPU:0"]


def test_busy_and_idle_share_match_brute_force(grid):
    red, busy, _ = grid
    assert red.busy_s() == pytest.approx(busy.sum() / 1e6, abs=2e-5)
    assert red.idle_share() == pytest.approx(1 - busy.mean(), abs=3e-4)
    assert 0.0 < red.idle_share() < 0.5


def test_kernel_time_matches_brute_force(grid):
    red, _, kern = grid
    assert red.op_seconds(KERNELS) == pytest.approx(kern / 1e6, abs=2e-5)
    n_kernels = sum(1 for evs in red.ops.values() for n, _, _ in evs
                    if "topk_pallas" in n)
    assert n_kernels >= 4                      # crude + refine per batch
    assert red.op_seconds(KERNELS) > 0.8 * red.busy_s()


def test_breakdown_lists_the_kernels_and_names_the_gaps(grid):
    red, busy, _ = grid
    b = red.breakdown(10)
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    top = {name for name, _ in b["device_ops"][:2]}
    assert top == {"crude_topk_pallas.1", "refine_topk_pallas.1"}
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    gaps = [s for _, s in b["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert sum(gaps) <= red.window_s - red.busy_s() + 1e-9
    assert all(name.startswith("bench.") or name == "no span"
               for name, _ in b["idle_gaps"])


def test_op_names_keep_the_instruction_name():
    assert tracing.op_name("%crude_topk_pallas.1 = (f32[64,10]) "
                           "custom-call(u8[8] %c)") == "crude_topk_pallas.1"
    assert tracing.op_name("fusion.3") == "fusion.3"


def test_a_trace_without_the_window_span_raises():
    with pytest.raises(ValueError):
        tracing.Reduced({"planes": []})


def _on_four_chips(trace):
    """The recorded trace with its device plane on four chips."""
    planes = [p for p in trace["planes"]
              if not tracing.DEVICE_PLANE.match(p["name"])]
    dev = [p for p in trace["planes"] if tracing.DEVICE_PLANE.match(p["name"])]
    assert len(dev) == 1
    return {"planes": planes + [dict(dev[0], name=f"/device:TPU:{i}")
                                for i in range(4)]}


def test_roofline_is_read_per_chip(trace):
    """The batch's least time is shared by the chips that run the search
    kernels: four chips that each take as long as one took for the whole
    batch read a quarter of its share."""
    ref = {"rows_scanned": np.array([1_000_000]),
           "scanned": np.full(64, 1_000_000),
           "passed": np.full(64, 950_000), "rows_passed": np.array([990_000])}
    ctx = {"reference": ref, "calls": [None] * 3, "batch": 64,
           "config": {"icq": {"train": {"num_codebooks": 8,
                                        "codebook_size": 256}}},
           "model": {"fast": np.isin(np.arange(8), (1, 5))},
           "device_kind": "TPU v5 lite"}
    read = cells.metric_reader("search_kernels_roofline")
    one, four = tracing.Reduced(trace), tracing.Reduced(_on_four_chips(trace))
    assert one.op_chips(KERNELS) == 1 and four.op_chips(KERNELS) == 4
    assert four.op_chips((r"^no-such-op$",)) == 0
    assert four.op_seconds(KERNELS) == pytest.approx(one.op_seconds(KERNELS))
    share = read(dict(ctx, trace=one))
    assert 0 < share < 100
    assert read(dict(ctx, trace=four)) == pytest.approx(share / 4)
