"""Validation of the trip-count-aware HLO cost analyzer (launch.hlo_cost)
against XLA's own counts on loop-free programs and against
scanned-vs-unrolled equivalence — the basis of the roofline terms."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed.sharding import make_mesh_auto, shard_map_compat
from repro.launch.hlo_cost import analyze_hlo


def _compile(f, *args):
    return jax.jit(f).lower(*args).compile()


def test_loop_free_matches_xla():
    def f(x, w):
        return jnp.tanh(x @ w) @ w.T

    x = jax.ShapeDtypeStruct((256, 512), jnp.float32)
    w = jax.ShapeDtypeStruct((512, 512), jnp.float32)
    c = _compile(f, x, w)
    mine = analyze_hlo(c.as_text())
    xla = c.cost_analysis()
    assert mine["flops"] == pytest.approx(xla["flops"], rel=1e-6)
    assert mine["flops"] == pytest.approx(2 * 2 * 256 * 512 * 512, rel=1e-6)


def test_scan_equals_unrolled():
    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    ws = jax.ShapeDtypeStruct((8, 256, 256), jnp.float32)

    def g_scan(x, ws):
        return jax.lax.scan(lambda h, w: (jnp.tanh(h @ w), None), x, ws)[0]

    def g_unroll(x, ws):
        h = x
        for i in range(8):
            h = jnp.tanh(h @ ws[i])
        return h

    ms = analyze_hlo(_compile(g_scan, x, ws).as_text())
    mu = analyze_hlo(_compile(g_unroll, x, ws).as_text())
    assert ms["flops"] == pytest.approx(mu["flops"], rel=1e-6)
    assert ms["flops"] == pytest.approx(8 * 2 * 128 * 256 * 256, rel=1e-6)
    # bytes: scan adds loop-carry traffic; must agree within 2x and both
    # scale with the trip count (XLA's builtin reports ~1/8 of this)
    assert 0.5 < ms["bytes"] / mu["bytes"] < 2.0


def test_nested_scan_multiplies():
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)

    def f(x):
        def outer(h, _):
            def inner(g, _):
                return jnp.tanh(g @ g), None
            h, _ = jax.lax.scan(inner, h, None, length=3)
            return h, None
        return jax.lax.scan(outer, x, None, length=5)[0]

    m = analyze_hlo(_compile(f, x).as_text())
    assert m["flops"] == pytest.approx(15 * 2 * 64 * 64 * 64, rel=1e-6)


def test_collectives_counted_with_multiplier():
    from jax.sharding import PartitionSpec as P
    mesh = make_mesh_auto((1,), ("d",))

    def h_fn(x):
        def body(c, _):
            s = jax.lax.psum(c, "d")
            return c + 0 * s, s
        out, ss = jax.lax.scan(body, x, None, length=5)
        return out, ss

    sm = shard_map_compat(h_fn, mesh, P("d"),
                          (P("d"), P(None, "d")))
    c = jax.jit(sm).lower(
        jax.ShapeDtypeStruct((16, 64), jnp.float32)).compile()
    m = analyze_hlo(c.as_text())
    assert m["collective_bytes"] == pytest.approx(5 * 16 * 64 * 4, rel=1e-6)
    assert "all-reduce" in m["collectives_by_op"]


def test_dryrun_exec_flops_vs_hlo_on_real_cell():
    """End-to-end audit: the measured (trip-count-corrected) HLO flops of
    a real train cell must land within 35% of the analytic 8/6*6ND
    estimate (slack: attention flops, CE head, z-loss, norms)."""
    import dataclasses
    from repro.configs import get_config
    from repro.configs.base import ShapeSpec
    from repro.launch.dryrun import exec_flops
    from repro.launch.steps import lower_cell, plan_cell
    mesh = make_mesh_auto((1, 1), ("data", "model"))
    cfg = dataclasses.replace(get_config("tinyllama-1.1b"), num_layers=2,
                              microbatch_size=2)
    shape = ShapeSpec(name="t", seq_len=512, global_batch=2, kind="train")
    plan = plan_cell(cfg, shape, mesh)
    compiled = lower_cell(plan).compile()
    m = analyze_hlo(compiled.as_text())
    ana = exec_flops(plan.cfg, shape)
    assert 0.65 < m["flops"] / ana < 1.35
