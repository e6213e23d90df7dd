"""The four-chip cell on the fused kernels: a tiny cell with ``chips: 4``
served by ``pallas`` through ``run.prepare`` is correct, and the
``shard.collective_share.batch`` reader's arithmetic on a synthetic
trace.  The cell runs on four virtual CPU devices in a subprocess, the
kernels in interpret mode, since this suite sees one device."""
import json
import os
import subprocess
import sys

import pytest
from tinycell import ROOT

from bench import cells, tracing

SCRIPT = r"""
import json, os, sys
sys.path[:0] = [os.environ["BENCH_ROOT"],
                os.path.join(os.environ["BENCH_ROOT"], "src"),
                os.path.join(os.environ["BENCH_ROOT"], "bench", "tests")]
from tinycell import tiny_cell
from bench import run

cell = tiny_cell("deep-sharded4.batch64")
cfg = cell["config"]
cfg["shapes"].update(n_base=8190)          # not a multiple of the 4 chips
st = run.prepare(cell, require_tpu=False,
                 overrides={"serve.backend": "pallas"})
engine = st["searcher"].engine
out = run.measure(st, 2**31 + 11, 0.5, False)
print("RESULT " + json.dumps({
    "backend": st["backend"], "view_backend": engine.served.backend,
    "mesh_devices": int(engine.mesh.devices.size),
    "codes_devices": len(engine.served.codes.sharding.device_set),
    "out": out}))
"""


@pytest.fixture(scope="module")
def sharded_run():
    env = dict(os.environ, BENCH_ROOT=ROOT, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = " ".join(filter(None, [
        env.get("XLA_FLAGS"), "--xla_force_host_platform_device_count=4"]))
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_the_four_chip_cell_is_served_by_the_fused_kernels(sharded_run):
    assert sharded_run["backend"] == "pallas"
    assert sharded_run["view_backend"] == "pallas"
    assert sharded_run["mesh_devices"] == 4
    assert sharded_run["codes_devices"] == 4


def test_the_four_chip_pallas_cell_is_correct(sharded_run):
    out = sharded_run["out"]
    assert out["device"]["count"] == 4
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["checks"]["ids_missed"]["value"] == 0


T0, T1 = 1_000, 101_000                 # the bench.window, ns


def _trace(*device_ops):
    planes = [{"name": "/host:CPU", "lines": [
        {"name": "python", "events": [["bench.window", T0, T1 - T0]]}]}]
    for i, ops in enumerate(device_ops):
        planes.append({"name": f"/device:TPU:{i}", "lines": [
            {"name": "XLA Ops",
             "events": [[n, s, e - s] for n, s, e in ops]}]})
    return {"planes": planes}


def _read(trace):
    return cells.metric_reader("shard.collective_share.batch")(
        {"trace": tracing.Reduced(trace)})


def test_collective_share_averages_collectives_and_busy_over_chips():
    chip0 = [("crude_topk_pallas.1", 2_000, 22_000),
             ("all-gather.25", 22_000, 23_000),
             ("all-gather-start.2", 23_000, 23_500),
             ("all-gather-done.2", 23_500, 24_000),
             ("refine_topk_pallas.1", 24_000, 64_000),
             ("psum.7", 64_000, 65_000),
             ("all-reduce.3", 65_000, 66_000),
             # a fusion whose name only holds the word is not one
             ("all-gather-fusion", 66_000, 67_000),
             # clipped at the window's end: 1_000 of it counts
             ("all-gather.26", T1 - 1_000, T1 + 5_000)]
    chip1 = [("crude_topk_pallas.1", 3_000, 23_000),
             ("all-gather.25", 23_000, 27_000)]
    coll = (1_000 + 500 + 500 + 1_000 + 1_000 + 1_000) + 4_000
    busy = (65_000 + 1_000) + 24_000
    assert _read(_trace(chip0, chip1)) == pytest.approx(
        100.0 * (coll / 2) / (busy / 2))


def test_collective_share_is_none_where_no_collective_ran():
    assert _read(_trace([("crude_topk_pallas.1", 2_000, 22_000)])) is None
    assert cells.metric_reader("shard.collective_share.batch")({}) is None
