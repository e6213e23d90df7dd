"""Checkpoint manager, fault-tolerant supervisor, heartbeat monitor,
elastic re-mesh planning, and the sharding rule tables."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed import (CheckpointManager, HeartbeatMonitor,
                               TrainSupervisor, plan_mesh_shape)
from repro.distributed import sharding as shrules


# ------------------------------------------------------------ checkpoint --

def _state(v=0.0):
    return {"params": {"w": jnp.full((4, 4), v), "b": jnp.zeros((4,))},
            "step": jnp.asarray(int(v), jnp.int32)}


def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    s = _state(3.0)
    mgr.save(10, s)
    step, restored = mgr.restore_latest(_state())
    assert step == 10
    np.testing.assert_array_equal(np.asarray(restored["params"]["w"]),
                                  np.full((4, 4), 3.0))


def test_checkpoint_retention(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for i in range(5):
        mgr.save(i, _state(float(i)))
    assert mgr.all_steps() == [3, 4]


def test_checkpoint_keep_period(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=1, keep_period=2)
    for i in range(5):
        mgr.save(i, _state(float(i)))
    assert set(mgr.all_steps()) == {0, 2, 4}


def test_corrupt_latest_falls_back(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, _state(1.0))
    mgr.save(2, _state(2.0))
    # corrupt the newest arrays file
    with open(os.path.join(str(tmp_path), "step_00000002", "arrays.npz"),
              "wb") as f:
        f.write(b"garbage")
    step, restored = mgr.restore_latest(_state())
    assert step == 1
    np.testing.assert_array_equal(np.asarray(restored["params"]["w"]),
                                  np.full((4, 4), 1.0))


def test_async_save(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save_async(7, _state(7.0))
    mgr.wait()
    assert mgr.all_steps() == [7]


# ------------------------------------------------------------- supervisor --

def test_supervisor_restart_after_fault(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=5)
    sup = TrainSupervisor(mgr, save_every=2, async_save=False)
    crashed = {"done": False}

    def fault_hook(step):
        if step == 5 and not crashed["done"]:
            crashed["done"] = True
            raise RuntimeError("simulated node loss")

    def step_fn(state, idx):
        return ({"params": {"w": state["params"]["w"] + 1.0},
                 "step": jnp.asarray(idx)}, {"loss": 1.0})

    state, rep = sup.run({"params": {"w": jnp.zeros(())},
                          "step": jnp.asarray(0)}, step_fn, 8,
                         fault_hook=fault_hook)
    assert rep.restarts == 1
    assert rep.final_step == 7
    # replayed steps 5.. from the step-4 checkpoint: total = 8 increments
    assert float(state["params"]["w"]) == 8.0


def test_supervisor_nan_quarantine(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    sup = TrainSupervisor(mgr, save_every=100, async_save=False)

    def step_fn(state, idx):
        loss = float("nan") if idx == 3 else 0.5
        return ({"params": {"w": state["params"]["w"] + 1.0},
                 "step": jnp.asarray(idx)}, {"loss": loss})

    state, rep = sup.run({"params": {"w": jnp.zeros(())},
                          "step": jnp.asarray(0)}, step_fn, 6)
    assert rep.nan_skips == 1
    assert float(state["params"]["w"]) == 5.0     # one update dropped


def test_supervisor_resume(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(3, {"params": {"w": jnp.asarray(42.0)}, "step": jnp.asarray(3)})
    sup = TrainSupervisor(mgr, save_every=100, async_save=False)

    def step_fn(state, idx):
        return ({"params": {"w": state["params"]["w"] + 1.0},
                 "step": jnp.asarray(idx)}, {"loss": 0.1})

    state, rep = sup.run({"params": {"w": jnp.zeros(())},
                          "step": jnp.asarray(0)}, step_fn, 6)
    assert rep.resumed_from == 3
    assert float(state["params"]["w"]) == 44.0    # steps 4,5 applied


def test_supervisor_restart_budget_exhausted(tmp_path):
    """A permanent fault propagates once ``max_restarts`` is spent —
    the supervisor never spins forever on a dead fleet."""
    mgr = CheckpointManager(str(tmp_path))
    sup = TrainSupervisor(mgr, save_every=1, max_restarts=2,
                          async_save=False)
    attempts = {"n": 0}

    def fault_hook(step):
        attempts["n"] += 1
        raise RuntimeError("permanent node loss")

    def step_fn(state, idx):
        return state, {"loss": 0.1}

    with pytest.raises(RuntimeError, match="permanent node loss"):
        sup.run({"params": {"w": jnp.zeros(())}}, step_fn, 4,
                fault_hook=fault_hook)
    assert attempts["n"] == 3          # initial try + max_restarts


def test_supervisor_always_writes_final_checkpoint(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    sup = TrainSupervisor(mgr, save_every=100, async_save=False)

    def step_fn(state, idx):
        return ({"params": {"w": state["params"]["w"] + 1.0}},
                {"loss": 0.1})

    sup.run({"params": {"w": jnp.zeros(())}}, step_fn, 3)
    # save_every never fired, but the final state is still durable
    assert 2 in mgr.all_steps()


# -------------------------------------------------------------- heartbeat --

def test_heartbeat_straggler_detection():
    mon = HeartbeatMonitor(num_hosts=4, straggler_factor=3.0)
    for step in range(8):
        for h in range(4):
            mon.beat(h, 1.0 if h != 2 else 5.0)
    assert mon.stragglers() == [2]


def test_heartbeat_window_trims_history():
    mon = HeartbeatMonitor(num_hosts=1, window=8)
    for i in range(50):
        mon.beat(0, float(i))
    assert len(mon._latency[0]) == 8
    assert mon._latency[0][-1] == 49.0


def test_heartbeat_no_beats_no_stragglers():
    # median of an empty fleet must not divide by zero or flag anyone
    mon = HeartbeatMonitor(num_hosts=3)
    assert mon.stragglers() == [] and mon.fleet_median() == 0.0


def test_heartbeat_dead_host():
    mon = HeartbeatMonitor(num_hosts=2, dead_after=10.0)
    now = 1000.0
    mon.beat(0, 1.0, now=now)
    mon.beat(1, 1.0, now=now - 60.0)
    mon._last_seen[1] = now - 60.0
    assert mon.dead(now=now) == [1]


# ---------------------------------------------------------------- elastic --

@pytest.mark.parametrize("n,divisors,expect", [
    (256, (16, 128), (16, 16)),
    (255, (16, 128), (8, 16)),     # lost a chip: pow2 floor 128 -> 8x16
    (8, (4,), (2, 4)),
    (8, (3,), (8, 1)),             # model must divide heads: falls to 1
])
def test_plan_mesh_shape(n, divisors, expect):
    assert plan_mesh_shape(n, model_divisors=divisors) == expect


# ------------------------------------------------------------- shardings --

def test_param_pspec_tables(key):
    from jax.sharding import AbstractMesh, PartitionSpec as P
    mesh = AbstractMesh((1, 1), ("data", "model"))

    class Leaf:
        def __init__(self, shape):
            self.shape = shape

    # embed (V, d) -> (model, data); divisibility guard passes at size 1
    spec = shrules.param_pspec(
        (jax.tree_util.DictKey("embed"),), Leaf((100, 64)), mesh)
    assert spec == P(None, None)   # axis size 1 -> replicated by guard

    mesh2 = AbstractMesh((2, 2), ("data", "model"))
    spec2 = shrules.param_pspec(
        (jax.tree_util.DictKey("embed"),), Leaf((100, 64)), mesh2)
    assert spec2 == P("model", "data")
    # odd vocab falls back to replicated on that dim
    spec3 = shrules.param_pspec(
        (jax.tree_util.DictKey("embed"),), Leaf((101, 64)), mesh2)
    assert spec3 == P(None, "data")


def test_every_smoke_param_gets_a_spec():
    """The rule table must cover every parameter of every architecture
    (falling back to replication is fine; crashing is not)."""
    from repro.configs import list_archs, smoke_config
    from repro.models import build_model
    from jax.sharding import AbstractMesh
    mesh = AbstractMesh((2, 2), ("data", "model"))
    for arch in list_archs():
        cfg = smoke_config(arch)
        model = build_model(cfg)
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        shardings = shrules.param_shardings(shapes, mesh)
        assert (jax.tree_util.tree_structure(shardings)
                == jax.tree_util.tree_structure(shapes)), arch
