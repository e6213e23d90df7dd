"""A cell that asks for four chips is served by an index row-sharded over
a mesh of four devices, reports four devices, and is held to the same
reference; a shard that answers nothing makes it not correct.  Driven on
four virtual CPU devices in a subprocess, since this suite sees one."""
import json
import os
import subprocess
import sys

import pytest
from tinycell import ROOT

SCRIPT = r"""
import json, os, sys
sys.path[:0] = [os.environ["BENCH_ROOT"],
                os.path.join(os.environ["BENCH_ROOT"], "src"),
                os.path.join(os.environ["BENCH_ROOT"], "bench", "tests")]
from tinycell import tiny_cell
from bench import run

cell = tiny_cell("sift1m-twostep.batch64")
cell["workload"] = dict(cell["workload"], chips=4)
st = run.prepare(cell, require_tpu=False)
engine = st["searcher"].engine
mesh = engine.mesh
sound = run.measure(st, 7, 0.5, False, keep=True)
engine.mark_shard_dead(1)
dead = run.measure(st, 7, 0.5, False)
one = run.prepare(tiny_cell("sift1m-twostep.batch64"), require_tpu=False)
print("RESULT " + json.dumps({
    "mesh_devices": None if mesh is None else int(mesh.devices.size),
    "mesh_axes": None if mesh is None else list(mesh.axis_names),
    "codes_devices": len(engine.served.codes.sharding.device_set),
    "sound": sound, "dead": dead,
    "one_chip_mesh": one["searcher"].engine.mesh is not None,
    "one_chip_devices": len(one["devices"])}))
"""


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, BENCH_ROOT=ROOT, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = " ".join(filter(None, [
        env.get("XLA_FLAGS"), "--xla_force_host_platform_device_count=4"]))
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_a_four_chip_cell_is_served_sharded_over_four_devices(runs):
    assert runs["mesh_devices"] == 4 and runs["mesh_axes"] == ["data"]
    assert runs["codes_devices"] == 4
    out = runs["sound"]
    assert out["device"]["count"] == 4
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0


def test_a_dead_shard_makes_a_four_chip_cell_not_correct(runs):
    out = runs["dead"]
    assert not out["correct"]
    missed = out["checks"]["ids_missed"]
    assert missed["value"] > missed["limit"]


def test_a_one_chip_cell_is_served_without_a_mesh(runs):
    assert not runs["one_chip_mesh"]
    assert runs["one_chip_devices"] == 1
