"""The harness finds every part of a cell by name, so a new traffic mix
or metric is a new file and a new entry, with no existing file edited;
and BENCHMARK.json keeps to the characters and keys its format allows."""
import json
import os
import re
import shutil

import pytest
from tinycell import ROOT, run_tiny

from bench import cells

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture
def bench_copy(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return tmp_path


def _snapshot(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = fh.read()
    return out


def test_a_new_traffic_file_and_entry_make_a_cell(bench_copy):
    before = _snapshot(bench_copy / "bench")
    mix = {"kind": "poisson", "rows": 2, "rate_hz": 50.0, "gap_seed": 3,
           "why": "a throwaway mix"}
    (bench_copy / "bench" / "traffic" / "throwaway.json").write_text(
        json.dumps(mix))
    spec = json.loads((bench_copy / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "sift1m-twostep.throwaway",
                              "config": "sift1m-icq64-twostep",
                              "traffic": "throwaway", "chips": 1,
                              "why": "test"})
    (bench_copy / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = cells.find_cell("sift1m-twostep.throwaway", str(bench_copy))
    assert cell["traffic"] == mix
    assert cell["config"]["name"] == "sift1m-icq64-twostep"
    # only the new file appeared; every existing file is as it was
    after = _snapshot(bench_copy / "bench")
    assert set(after) - set(before) == {os.path.join("traffic",
                                                     "throwaway.json")}
    assert all(after[p] == before[p] for p in before)


REVERSE_KIND = '''
"""A closed loop that walks the pool backwards."""
import numpy as np

from bench import loadgen


def warm(searcher, pool, mix):
    res = searcher.search(pool[:int(mix["batch"])])
    np.asarray(res.indices)
    return {"searcher": searcher, "backend": res.meta.backend}


def window(state, pool, mix, seconds, seed, span):
    b, n = int(mix["batch"]), pool.shape[0]

    def call(i):
        rows = (n - 1 - (i * b + np.arange(b))) % n
        res = state["searcher"].search(pool[rows])
        return {"rows": rows, "ids": np.asarray(res.indices),
                "dists": np.asarray(res.distances),
                "backend": res.meta.backend}

    calls, elapsed = loadgen.run_closed(call, seconds)
    return {"answers": calls, "attempted": len(calls) * b, "lost": 0,
            "metrics": {"qps": len(calls) * b / elapsed}, "layer": {}}


def close(state):
    state.clear()
'''


def test_a_new_traffic_kind_is_a_file_and_runs(bench_copy):
    """A kind of traffic the harness has never seen: its driver module,
    a mix that names it and a cell, and the whole run goes through."""
    before = _snapshot(bench_copy / "bench")
    (bench_copy / "bench" / "traffic_kinds" / "reverse.py").write_text(
        REVERSE_KIND)
    (bench_copy / "bench" / "traffic" / "rev8.json").write_text(
        json.dumps({"kind": "reverse", "batch": 8, "why": "test"}))
    spec = json.loads((bench_copy / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "sift1m-twostep.rev8",
                              "config": "sift1m-icq64-twostep",
                              "traffic": "rev8", "chips": 1, "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] in ("qps", "recall_at_10"):
            m["workloads"].append("sift1m-twostep.rev8")
    (bench_copy / "BENCHMARK.json").write_text(json.dumps(spec))
    out = run_tiny("sift1m-twostep.rev8", root=str(bench_copy))
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"qps", "recall_at_10", "setup_s"}
    after = _snapshot(bench_copy / "bench")
    assert set(after) - set(before) == {
        os.path.join("traffic_kinds", "reverse.py"),
        os.path.join("traffic", "rev8.json")}
    assert all(after[p] == before[p] for p in before)


def test_a_traffic_file_naming_no_driver_is_refused(bench_copy):
    (bench_copy / "bench" / "traffic" / "odd.json").write_text(
        json.dumps({"kind": "no-such-kind"}))
    with pytest.raises(cells.CellError, match="no-such-kind"):
        cells.load_traffic("odd", str(bench_copy))


def test_a_new_metric_reader_is_found_by_name(bench_copy):
    (bench_copy / "bench" / "metrics" / "x.new_metric.py").write_text(
        "def read(ctx):\n    return ctx.get('v')\n")
    read = cells.metric_reader("x.new_metric", str(bench_copy))
    assert read({"v": 3.0}) == 3.0 and read({}) is None


def test_unknown_parts_raise():
    with pytest.raises(cells.CellError):
        cells.find_cell("no-such-cell")
    with pytest.raises(cells.CellError):
        cells.load_traffic("no-such-mix")
    with pytest.raises(cells.CellError):
        cells.metric_reader("no-such-metric")


def test_every_cell_resolves_and_every_metric_has_a_reader():
    spec = cells.load_benchmark()
    for w in spec["workloads"]:
        cell = cells.find_cell(w["name"])
        assert any(m["name"] == "setup_s" for m in cell["end_to_end"])
        assert len(cell["end_to_end"]) >= 2 and cell["per_layer"]
    for m in spec["per_layer"]:
        assert callable(cells.metric_reader(m["name"]))


def test_benchmark_json_keeps_to_its_format():
    spec = cells.load_benchmark()
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["command"][1].startswith(spec["paths"][0] + "/")
    names = [c["name"] for c in spec["configs"]] + \
        [w["name"] for w in spec["workloads"]] + \
        [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["why"]) <= 200
        assert c["file"].startswith("bench/") and len(c["source"]) <= 200
        assert cells.load_config_file(os.path.join(ROOT, c["file"]))[
            "name"] == c["name"]
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    four = sum(w["chips"] == 4 for w in spec["workloads"])
    assert four <= max(1, len(spec["workloads"]) // 2)
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in spec["end_to_end"]}
    for m in spec["per_layer"]:
        assert m["moves"] in e2e and m["layer"]
