"""End-to-end training driver.

Runs real steps on the available devices (CPU smoke -> TPU pod with the
same code path): data pipeline -> sharded train_step -> checkpointing /
fault-tolerant supervision.  The production meshes are exercised without
hardware by ``dryrun.py``; this driver actually executes.

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
        --steps 20 --seq-len 128 --global-batch 8 --smoke

``--icq`` runs the *retrieval* pipeline instead (no LM): the trainer
layer's scan-compiled ``fit`` (DESIGN.md §9) on a synthetic Table-1
dataset — optionally data-parallel over ``--icq-shards`` devices —
then builds a serving index, grows it with ``Index.add``, and
round-trips a query batch:

    PYTHONPATH=src python -m repro.launch.train --icq --icq-epochs 4
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python -m repro.launch.train --icq --icq-shards 4
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, smoke_config
from repro.configs.base import ShapeSpec
from repro.data.pipeline import TokenPipeline
from repro.distributed import CheckpointManager, TrainSupervisor
from repro.distributed import sharding as shrules
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import (batch_shardings, batch_struct,
                                build_train_step, num_microbatches)


def make_host_batch(pipe, cfg, shape, n_micro, step):
    raw = pipe.batch(step)
    B = shape.global_batch

    def shape_mb(x):
        return x.reshape((n_micro, B // n_micro) + x.shape[1:])

    batch = {k: shape_mb(v) for k, v in raw.items()}
    if cfg.frontend == "vision_stub":
        v = cfg.num_vision_tokens
        batch["tokens"] = batch["tokens"][..., : shape.seq_len - v]
        batch["labels"] = batch["labels"][..., : shape.seq_len - v]
        batch["patch_emb"] = np.random.default_rng(step).standard_normal(
            (n_micro, B // n_micro, v, cfg.vision_dim)).astype(np.float32)
    if cfg.encdec:
        batch["audio_emb"] = np.random.default_rng(step).standard_normal(
            (n_micro, B // n_micro, cfg.encoder_seq_len, cfg.d_model)
        ).astype(np.float32)
    return batch


def icq_config_from_args(args):
    """Resolve the run's ``repro.api.ICQConfig``: ``--config path.json``
    (validated, schema-versioned) or the CLI default, with the legacy
    flags applied as dotted overrides — a flag left at its ``None``
    default defers to the config."""
    from repro.api import ICQConfig, TrainConfig, ServeConfig

    if args.config is not None:
        cfg = ICQConfig.load(args.config)
    else:                       # the historical CLI defaults
        cfg = ICQConfig(
            train=TrainConfig(codebook_size=64, epochs=3, batch_size=256),
            serve=ServeConfig(topk=20, backend="jnp"))
    overrides = {}
    if args.icq_epochs is not None:
        overrides["train.epochs"] = args.icq_epochs
    if args.icq_batch is not None:
        overrides["train.batch_size"] = args.icq_batch
    if args.icq_index is not None:
        overrides["index.kind"] = args.icq_index
    return cfg.with_overrides(overrides)


def run_icq(args):
    """Train -> index -> add -> query -> (save): the retrieval pipeline
    through the front-door api (``repro.api.icq_session``, docs/api.md)
    — scan epochs, optional data-parallel mesh, tiled encoding engine,
    incremental index build, persistent artifacts."""
    import jax.numpy as jnp

    from repro.api import icq_session
    from repro.data import make_table1_dataset
    from repro.index import recall_at

    cfg = icq_config_from_args(args)
    xtr, ytr, xte, yte = make_table1_dataset(args.icq_dataset)
    xtr, ytr = xtr[: args.icq_n], ytr[: args.icq_n]
    n_held = max(args.icq_add, 1)
    x_held, xtr = xtr[-n_held:], xtr[:-n_held]       # rows added post-build
    ytr = ytr[:-n_held]

    mesh = None
    if args.icq_shards > 1:
        if len(jax.devices()) < args.icq_shards:
            raise SystemExit(
                f"--icq-shards {args.icq_shards} needs that many devices; "
                "on CPU set XLA_FLAGS=--xla_force_host_platform_device_"
                f"count={args.icq_shards}")
        mesh = shrules.make_mesh_auto((args.icq_shards,), ("data",))

    session = icq_session(cfg)
    t0 = time.time()
    model = session.fit(xtr, ytr, key=jax.random.PRNGKey(args.seed),
                        mesh=mesh, verbose=True)
    print(f"icq: fit n={xtr.shape[0]} epochs={cfg.train.epochs} "
          f"shards={args.icq_shards} in {time.time() - t0:.1f}s; "
          f"psi={int(model.structure.xi.sum())}/{cfg.train.d} "
          f"fast={int(model.structure.fast_mask.sum())}"
          f"/{cfg.train.num_codebooks}")

    searcher = session.index(mesh=mesh,
                             key=jax.random.PRNGKey(args.seed + 1))
    n0 = searcher.n
    searcher.add(x_held)                             # incremental build
    res = searcher.search(xte[:64])
    jax.block_until_ready(res.indices)
    # the held-out rows must be findable: query with themselves
    self_res = searcher.search(x_held[: min(n_held, 16)])
    self_ids = jnp.arange(n0, n0 + min(n_held, 16))[:, None]
    hit = float(recall_at(self_res.indices, self_ids))
    print(f"icq: index={cfg.index.kind} grown {n0} -> {searcher.n}; "
          f"query batch ok (pass_rate={float(res.pass_rate):.3f}); "
          f"added-row self-recall@{cfg.serve.topk}={hit:.3f}")

    if args.save_artifacts:
        path = searcher.save(args.save_artifacts)
        print(f"icq: artifacts (config hash "
              f"{cfg.config_hash()[:12]}) -> {path}; reload with "
              "launch/serve.py --load-artifacts or "
              "repro.api.load_ann_engine")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config of the arch family (CPU-sized)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--save-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--icq", action="store_true",
                    help="run the retrieval trainer pipeline (no LM): "
                         "scan-compiled fit -> index -> add -> query")
    ap.add_argument("--config", default=None,
                    help="repro.api ICQConfig JSON driving the --icq run "
                         "(docs/api.md); the --icq-* flags below override "
                         "individual fields")
    ap.add_argument("--save-artifacts", default=None, metavar="DIR",
                    help="after the --icq run, persist config + model + "
                         "index (repro.api.Artifacts); reload with "
                         "launch/serve.py --load-artifacts DIR")
    ap.add_argument("--icq-dataset", default="dataset2")
    ap.add_argument("--icq-n", type=int, default=4000)
    ap.add_argument("--icq-epochs", type=int, default=None,
                    help="override train.epochs (config default: 3)")
    ap.add_argument("--icq-batch", type=int, default=None,
                    help="override train.batch_size (config default: 256)")
    ap.add_argument("--icq-shards", type=int, default=1,
                    help="data-parallel training/serving mesh size")
    ap.add_argument("--icq-index", default=None,
                    choices=["flat", "two-step", "ivf"],
                    help="override index.kind (config default: two-step)")
    ap.add_argument("--icq-add", type=int, default=64,
                    help="held-out rows appended via Index.add post-build")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.icq:
        run_icq(args)
        return
    if args.arch is None:
        ap.error("--arch is required unless --icq is given")

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    shape = ShapeSpec(name="cli", seq_len=args.seq_len,
                      global_batch=args.global_batch, kind="train")
    mesh = make_host_mesh()
    dp = shrules.axis_size(mesh, "data")
    n_micro = num_microbatches(cfg, shape, dp)

    train_step, model, opt, init_opt = build_train_step(
        cfg, n_micro=n_micro, mesh=mesh)
    params = model.init(jax.random.PRNGKey(0))
    opt_state = init_opt(params)

    bspec = batch_struct(cfg, shape, n_micro, train=True)
    b_shard = batch_shardings(bspec, mesh, train=True)
    step_jit = jax.jit(train_step, donate_argnums=(0, 1))

    pipe = TokenPipeline(vocab_size=cfg.vocab_size,
                         seq_len=shape.seq_len,
                         global_batch=shape.global_batch)
    ckpt = CheckpointManager(args.ckpt_dir, keep=3)
    sup = TrainSupervisor(ckpt, save_every=args.save_every)

    state = {"params": params, "opt": opt_state}

    def one_step(state, idx):
        batch = make_host_batch(pipe, cfg, shape, n_micro, idx)
        batch = jax.tree.map(
            lambda x, s: jax.device_put(x, s), batch, b_shard)
        t0 = time.time()
        p, o, metrics = step_jit(state["params"], state["opt"], batch)
        loss = float(metrics["loss"])
        print(f"step {idx:5d} loss={loss:8.4f} "
              f"gnorm={float(metrics['gnorm']):7.3f} "
              f"dt={time.time() - t0:5.2f}s")
        return {"params": p, "opt": o}, {"loss": loss}

    state, report = sup.run(state, one_step, args.steps)
    print(f"done: final_step={report.final_step} restarts={report.restarts} "
          f"resumed_from={report.resumed_from}")


if __name__ == "__main__":
    main()
