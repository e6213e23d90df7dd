"""Row-sharded two-step search on the fused Pallas kernels
(``index/sharded.py``, ``backend="pallas"``) on four virtual CPU
devices, the kernels in interpret mode: ids bitwise equal to the
single-device Pallas engine, the plain reference's ``ids_missed`` 0 on
its answers, a dead shard's survivors merged, a filter served by the jnp
body, and a kernel fault failed over to it.  Runs in a subprocess: this
suite must keep seeing a single device (conftest)."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = textwrap.dedent("""
    import json, os, sys
    sys.path.insert(0, os.environ["REPO_ROOT"])
    import jax, jax.numpy as jnp, numpy as np
    assert len(jax.devices()) == 4, jax.devices()
    from bench import reference, verdict
    from repro.api import AnnEngine
    from repro.core import icq as icq_mod
    from repro.core.encode import pack_nibbles
    from repro.index import TwoStep
    from repro.resilience import FaultInjector, FaultSpec

    rng = np.random.default_rng(0)
    mesh = jax.make_mesh((4,), ("data",))
    K, m, d, nq, topk = 8, 16, 8, 9, 17
    C = jnp.asarray(rng.standard_normal((K, m, d)).astype(np.float32) * 0.3)
    fast = np.isin(np.arange(K), (1, 5))
    st = icq_mod.ICQStructure(xi=jnp.ones((d,), bool),
                              fast_mask=jnp.asarray(fast),
                              sigma=jnp.asarray(1.0, jnp.float32))
    q = jnp.asarray(rng.standard_normal((nq, d)).astype(np.float32))
    out = {}

    def build(codes, backend="pallas", **kw):
        return TwoStep.build(jnp.asarray(codes), C, st, topk=topk,
                             backend=backend, **kw)

    def pair(r1, r4):
        i1, i4 = np.asarray(r1.indices), np.asarray(r4.indices)
        d1, d4 = np.asarray(r1.distances), np.asarray(r4.distances)
        fin = np.isfinite(d1)
        return {"ids_equal": bool(np.array_equal(i1, i4)),
                "inf_equal": bool(np.array_equal(fin, np.isfinite(d4))),
                "dist_diff": float(np.max(np.abs(d1[fin] - d4[fin]),
                                          initial=0.0)),
                "pass": [float(r1.pass_rate), float(r4.pass_rate)],
                "ops": [float(r1.avg_ops), float(r4.avg_ops)]}

    rows = {}
    for n, lut, bits in [(4096, "f32", 8), (4099, "f32", 8),
                         (1237, "int8", 8), (1237, "f32", 4)]:
        codes = rng.integers(0, m, (n, K)).astype(np.uint8)
        rows[n] = codes
        stored = pack_nibbles(jnp.asarray(codes), K) if bits == 4 else codes
        idx = build(stored, lut_dtype=lut, code_bits=bits)
        view = idx.shard(mesh)
        tag = f"{n}-{lut}-{bits}"
        out[tag] = dict(pair(idx.search(q), view.search(q)),
                        backend=view.backend)

    # the plain reference on the sharded answers
    codes = rows[4099]
    r4 = build(codes).shard(mesh).search(q)
    ref = reference.search(np.asarray(q), codes,
                           {"C": np.asarray(C), "fast": fast, "sigma": 1.0},
                           topk=topk, answers=np.asarray(r4.indices))
    out["ids_missed"] = verdict.ids_missed(
        np.asarray(r4.indices), ref["answer_dists"], ref["dists"],
        ref["lut_range"], ref["reachable"])

    # dead shards: 1 (rows 1025..2049) and 3 (the tail, with pad rows)
    ns = -(-4099 // 4)
    for s in (1, 3):
        view = build(codes).shard(mesh).mark_shard_dead(s)
        keep = np.array([i for i in range(4099)
                         if not s * ns <= i < (s + 1) * ns])
        ref = build(codes[keep]).search(q)
        r = view.search(q)
        out[f"dead{s}"] = dict(pair(ref._replace(indices=jnp.asarray(
            keep[np.asarray(ref.indices)])), r), coverage=view.coverage)

    # a filter under the mesh: the jnp body serves it on either backend
    pred = rng.random(4099) < 0.5
    view = build(codes).shard(mesh)
    out["filter"] = dict(pair(build(codes, "jnp").search(q, filter=pred),
                              view.search(q, filter=pred)),
                         backend=view.backend)
    try:
        AnnEngine(build(codes), mesh=mesh).search(q, filter=pred)
        out["engine_filter"] = "served"
    except ValueError as e:
        out["engine_filter"] = str(e)

    # the engine on the mesh, then a kernel fault failed over to jnp
    eng = AnnEngine(build(codes), mesh=mesh)
    r = eng.search(q)
    out["engine"] = dict(pair(build(codes).search(q), r),
                         backend=r.meta.backend, cols=eng._kernel_cols("full"))
    inj = FaultInjector(seed=0, spec=FaultSpec(p_raise=1.0,
                                               targets=("kernels.",)))
    eng = AnnEngine(build(codes), mesh=mesh, fault_injector=inj)
    with inj.installed():
        r = eng.search(q)
    out["failover"] = dict(pair(build(codes, "jnp").search(q), r),
                           backend=r.meta.backend,
                           view_backend=eng.served.backend,
                           failovers=eng.stats["failovers"],
                           mesh_devices=int(eng.mesh.devices.size))
    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def out():
    env = dict(os.environ, REPO_ROOT=ROOT, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = " ".join(filter(None, [
        env.get("XLA_FLAGS"), "--xla_force_host_platform_device_count=4"]))
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


# Each shard builds the query's LUTs inside the SPMD program, where XLA
# may reassociate the LUT einsum: distances agree to f32 rounding of
# values of order 1-10, not bitwise.  Under int8 tables that rounding
# reaches the per-query calibration, so a row at the margin may pass on
# one side only: the pass rate agrees to one row in the 9 x 1237.
DIST_TOL = 1e-5
PASS_TOL = 1.0 / (9 * 1237)


def _same(p, *, pass_tol=0.0):
    assert p["ids_equal"] and p["inf_equal"]
    assert p["dist_diff"] <= DIST_TOL
    assert abs(p["pass"][0] - p["pass"][1]) <= pass_tol
    assert abs(p["ops"][0] - p["ops"][1]) <= 6 * pass_tol


@pytest.mark.parametrize("tag", ["4096-f32-8", "4099-f32-8", "1237-int8-8",
                                 "1237-f32-4"])
def test_sharded_pallas_ids_equal_the_single_device_pallas_engine(out, tag):
    assert out[tag]["backend"] == "pallas"
    _same(out[tag], pass_tol=PASS_TOL if "int8" in tag else 0.0)


def test_the_reference_misses_no_id_of_the_sharded_answers(out):
    assert out["ids_missed"] == 0


@pytest.mark.parametrize("shard", [1, 3])
def test_a_dead_shard_returns_the_surviving_shards_merge(out, shard):
    p = out[f"dead{shard}"]
    _same(p, pass_tol=1.0)      # pass rates count over different n
    assert 0.7 < p["coverage"] < 0.8


def test_a_filter_under_the_mesh_falls_back_to_the_jnp_body(out):
    assert out["filter"]["backend"] == "pallas"
    _same(out["filter"], pass_tol=1.0)   # the jnp pass rate is its own
    assert "backend='jnp'" in out["engine_filter"]


def test_the_engine_serves_the_mesh_on_the_kernels(out):
    e = out["engine"]
    assert e["backend"] == "pallas"
    assert e["cols"] == [2 * 16, 6 * 16]
    _same(e)


def test_a_kernel_fault_under_the_mesh_fails_over_to_the_jnp_body(out):
    f = out["failover"]
    assert f["failovers"] == 1 and f["mesh_devices"] == 4
    assert f["backend"] == "jnp" and f["view_backend"] == "jnp"
    assert f["ids_equal"]
    assert np.isclose(f["pass"][0], f["pass"][1])
