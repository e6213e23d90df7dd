"""Compiled epoch driver (DESIGN.md §9): ``lax.scan`` over pre-permuted
device-resident batches with donated train state, optionally
data-parallel over a mesh's ``data`` axis.

The seed ``fit`` dispatched one jitted step per batch from the host —
per-batch dispatch overhead, host-side fancy indexing for every batch,
and a hardcoded shuffle seed.  This driver:

  1. threads the *caller's* key: one split for init, one chain for the
     per-epoch permutations, so runs are actually seeded;
  2. permutes on device and reshapes into an (nb, bs, ...) batch stack,
     then runs the whole epoch as ONE compiled ``lax.scan`` with the
     train state donated (``jit(..., donate_argnums)``) — no per-batch
     host round-trips, no buffer churn;
  3. with ``mesh`` given (must carry a ``data`` axis), wraps the epoch
     in ``shard_map``: the batch dimension of every scan step is
     sharded over ``data``, the step pmeans gradients and consumes
     global batch moments (``make_train_step(axis_name="data")``), and
     parameters / optimizer / variance state stay replicated — the
     ``distributed/sharding.py`` shims handle jax-version differences.

Fresh variance state per epoch (the seed semantics) is kept: Lambda
tracks the *current* embedding distribution, not a stale average.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import variance
from repro.distributed.sharding import axis_size, shard_map_compat
from repro.trainer import joint
from repro.trainer.base import ICQModel


def compile_epoch(step, d: int, *, mesh=None, donate: bool = True):
    """Compile ``step`` into an epoch function.

    step:  (params, opt_state, var_state, (x, y)) -> (params, opt_state,
           var_state, metrics) — from ``joint.make_train_step`` (built
           with ``axis_name="data"`` when ``mesh`` is given).
    d:     embedding dim (fresh variance state per epoch).

    Returns ``epoch_fn(params, opt_state, xb, yb)`` -> (params,
    opt_state, var_state, last_metrics) where xb (nb, bs, ...) /
    yb (nb, bs) are the epoch's pre-permuted batch stacks.  The input
    params/opt_state buffers are donated.
    """
    def epoch_body(params, opt_state, xb, yb):
        def body(carry, batch):
            p, o, v = carry
            p, o, v, mets = step(p, o, v, batch)
            return (p, o, v), mets

        carry0 = (params, opt_state, variance.init_state(d))
        (p, o, v), mets = jax.lax.scan(body, carry0, (xb, yb))
        return p, o, v, jax.tree.map(lambda a: a[-1], mets)

    fn = epoch_body
    if mesh is not None:
        if "data" not in mesh.axis_names:
            raise ValueError("epoch driver needs a mesh with a 'data' axis")
        fn = shard_map_compat(
            epoch_body, mesh,
            in_specs=(P(), P(), P(None, "data"), P(None, "data")),
            out_specs=(P(), P(), P(), P()))
    return jax.jit(fn, donate_argnums=(0, 1) if donate else ())


def epoch_batches(key, xs, ys, batch_size: int):
    """Device-side permute + reshape into the epoch's batch stacks.

    Returns (xb (nb, bs, ...), yb (nb, bs)) with nb = n // bs full
    batches (the permutation's tail rows beyond nb*bs are dropped for
    this epoch, as in the seed loop)."""
    n = xs.shape[0]
    bs = max(min(batch_size, n), 1)
    nb = n // bs
    perm = jax.random.permutation(key, n)[: nb * bs]
    xb = jnp.asarray(xs)[perm].reshape((nb, bs) + xs.shape[1:])
    yb = jnp.asarray(ys)[perm].reshape((nb, bs))
    return xb, yb


def fit(key, xs, ys, icq_cfg, *, embed_kind="linear", num_classes=10,
        img_hw=None, channels=None, mode="icq", epochs=5, batch_size=256,
        lr=1e-3, tau=1.0, verbose=False, mesh=None,
        encode_batch: int = 8192, encode_backend: str = "auto",
        donate: bool = True, ckpt_dir: Optional[str] = None,
        save_every: int = 1, max_restarts: int = 3, heartbeat=None,
        fault_hook=None) -> ICQModel:
    """Scan-compiled training over (xs, ys) arrays -> fitted ICQModel.

    The drop-in successor of the seed host loop: same losses, same
    state transitions, but the whole epoch runs as one compiled scan
    (donated state) and the shuffle stream is derived from ``key`` —
    two calls with different keys draw different permutations and
    different init, two calls with the same key are identical.

    mesh:  optional mesh with a ``data`` axis — data-parallel training
           via shard_map with pmean'd gradients; ``batch_size`` must
           divide by the axis size.  Results match single-device
           training up to float reassociation.

    ckpt_dir (docs/robustness.md): supervised training — the epoch
    loop runs under ``distributed.TrainSupervisor`` with per-epoch
    checkpoints every ``save_every`` epochs, NaN-epoch quarantine, and
    up to ``max_restarts`` restore-and-replay restarts.  A killed fit
    re-invoked with the *same key and data* resumes from the newest
    checkpoint and produces bitwise-identical final codebooks: the
    checkpointed state carries the post-epoch rng, so the replayed
    shuffle chain is exactly the uninterrupted one.  Donation is
    disabled (restart replay needs the pre-epoch buffers alive).
    ``heartbeat`` (a ``distributed.HeartbeatMonitor``) gets a
    ``beat(0, epoch_seconds)`` per epoch; ``fault_hook(epoch)`` may
    raise to inject node loss (the chaos tests drive it).
    """
    n = xs.shape[0]
    d_raw = xs.shape[-1] if xs.ndim == 2 else None
    k_init, k_shuffle = jax.random.split(key)
    state = joint.init_train_state(
        k_init, icq_cfg, embed_kind=embed_kind, d_raw=d_raw,
        num_classes=num_classes, img_hw=img_hw, channels=channels,
        mode=mode, lr=lr,
        sample_batch=(xs[:min(n, 4096)], ys[:min(n, 4096)]))
    axis = "data" if mesh is not None else None
    bs = max(min(batch_size, n), 1)
    if mesh is not None and bs % axis_size(mesh, "data") != 0:
        raise ValueError(
            f"batch_size={bs} must divide over the {axis_size(mesh, 'data')}"
            "-way 'data' axis for the sharded epoch driver")
    step = joint.make_train_step(icq_cfg, state["embed_apply"], state["opt"],
                                 mode, state["pq_mask"], tau, axis_name=axis,
                                 unit=state["unit"])
    if ckpt_dir is not None:
        donate = False        # restart replay needs pre-epoch buffers
    epoch_fn = compile_epoch(step, icq_cfg.d, mesh=mesh, donate=donate)

    if ckpt_dir is not None:
        params, var_state = _supervised_loop(
            ckpt_dir, epoch_fn, state, k_shuffle, xs, ys, bs, epochs,
            save_every=save_every, max_restarts=max_restarts,
            heartbeat=heartbeat, fault_hook=fault_hook, verbose=verbose)
    else:
        params, opt_state = state["params"], state["opt_state"]
        var_state = state["var_state"]
        rng = k_shuffle
        for ep in range(epochs):
            rng, k = jax.random.split(rng)
            xb, yb = epoch_batches(k, xs, ys, bs)
            params, opt_state, var_state, mets = epoch_fn(params, opt_state,
                                                          xb, yb)
            if verbose:
                print(f"  epoch {ep}: " + " ".join(
                    f"{name}={float(v):.4f}" for name, v in mets.items()))
    return joint.finalize(params, state["embed_apply"], var_state, icq_cfg,
                          xs, unit=state["unit"], mode=mode,
                          encode_batch=encode_batch,
                          encode_backend=encode_backend)


def _supervised_loop(ckpt_dir, epoch_fn, state, k_shuffle, xs, ys, bs,
                     epochs, *, save_every, max_restarts, heartbeat,
                     fault_hook, verbose):
    """Run the epoch loop under ``TrainSupervisor`` (one supervisor
    step == one epoch).  Returns (params, var_state) after the final
    epoch — resumed or not, the state transitions are the ones the
    plain loop would have made."""
    import time

    from repro.distributed import CheckpointManager, TrainSupervisor

    sup = TrainSupervisor(CheckpointManager(ckpt_dir),
                          save_every=save_every,
                          max_restarts=max_restarts, async_save=False)

    def step_fn(s, ep):
        t0 = time.perf_counter()
        rng, k = jax.random.split(s["rng"])
        xb, yb = epoch_batches(k, xs, ys, bs)
        params, opt_state, var_state, mets = epoch_fn(
            s["params"], s["opt_state"], xb, yb)
        jax.block_until_ready(params)
        if heartbeat is not None:
            heartbeat.beat(0, time.perf_counter() - t0)
        if verbose:
            print(f"  epoch {ep}: " + " ".join(
                f"{name}={float(v):.4f}" for name, v in mets.items()))
        # the supervisor's NaN quarantine reads 'loss'; the joint
        # trainer calls its total 'total'
        metrics = dict(mets)
        metrics["loss"] = metrics.get("total", 0.0)
        return ({"params": params, "opt_state": opt_state,
                 "var_state": var_state, "rng": rng}, metrics)

    state0 = {"params": state["params"], "opt_state": state["opt_state"],
              "var_state": state["var_state"], "rng": k_shuffle}
    final, _report = sup.run(state0, step_fn, epochs,
                             fault_hook=fault_hook)
    return final["params"], final["var_state"]
