"""End-to-end driver: train a retrieval coder (embedding + ICQ quantizer)
with checkpointed, scan-compiled training, build the index, grow it
incrementally, and evaluate — the paper's workload on the framework's
full substrate (DESIGN.md §9).

Each epoch is ONE compiled ``lax.scan`` over pre-permuted
device-resident batches (``trainer.compile_epoch``, donated state) —
the host only touches the loop to checkpoint between epochs.  Export
runs the tiled ICM encoding engine; the last rows are held out of the
initial build and appended afterwards through ``Index.add`` to show the
incremental path produces the same serving surface.

    PYTHONPATH=src python examples/train_icq_retrieval.py --epochs 8
"""
import argparse
import time

import jax

from repro.api import Artifacts, ICQConfig, ServeConfig, TrainConfig
from repro.distributed import CheckpointManager
from repro.index import adc_search, make_index, mean_average_precision
from repro.trainer import (compile_epoch, epoch_batches, finalize,
                           init_train_state, make_train_step)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="dataset2")
    ap.add_argument("--epochs", type=int, default=8)
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="/tmp/icq_retrieval_ckpt")
    ap.add_argument("--hold-out", type=int, default=256,
                    help="rows appended via Index.add after the build")
    ap.add_argument("--save-artifacts", default=None, metavar="DIR",
                    help="persist config + model + index at the end")
    args = ap.parse_args()

    from repro.data import make_table1_dataset
    xtr, ytr, xte, yte = make_table1_dataset(args.dataset)
    # the api config is the source of truth; this example drives the
    # trainer layer underneath it by hand to thread checkpointing
    api_cfg = ICQConfig(train=TrainConfig(
        d=16, num_codebooks=8, codebook_size=64, num_fast=2,
        epochs=args.epochs, batch_size=args.batch_size),
        serve=ServeConfig(topk=50, backend="jnp"))
    cfg = api_cfg.train.hyperparams(icm_iters=api_cfg.encode.icm_iters)

    # explicit epoch loop (vs trainer.fit) to thread checkpointing; the
    # per-epoch work is still one compiled scan with donated state
    key = jax.random.PRNGKey(0)
    k_init, k_shuffle = jax.random.split(key)
    state = init_train_state(
        k_init, cfg, embed_kind="linear", d_raw=64, num_classes=10,
        mode="icq", sample_batch=(xtr[:4096], ytr[:4096]))
    step = make_train_step(cfg, state["embed_apply"], state["opt"], "icq",
                           None, unit=state["unit"])
    epoch_fn = compile_epoch(step, cfg.d)
    params, opt_state = state["params"], state["opt_state"]
    var_state = state["var_state"]
    ckpt = CheckpointManager(args.ckpt_dir, keep=2)
    start_ep, restored = ckpt.restore_latest(
        {"params": params, "opt": opt_state})
    if start_ep is not None:
        params, opt_state = restored["params"], restored["opt"]
        print(f"resumed from epoch {start_ep}")

    t0 = time.time()
    for ep in range((start_ep + 1) if start_ep is not None else 0,
                    args.epochs):
        xb, yb = epoch_batches(jax.random.fold_in(k_shuffle, ep), xtr, ytr,
                               args.batch_size)
        params, opt_state, var_state, mets = epoch_fn(params, opt_state,
                                                      xb, yb)
        ckpt.save_async(ep, {"params": params, "opt": opt_state})
        print(f"epoch {ep}: total={float(mets['total']):.4f} "
              f"l_e={float(mets['l_e']):.4f} l_c={float(mets['l_c']):.4f} "
              f"psi={int(mets['psi_size'])}")
    ckpt.wait()
    print(f"train {time.time() - t0:.1f}s (one compiled scan per epoch)")

    # hold the tail out of the export, append it through the engine
    n_built = xtr.shape[0] - args.hold_out
    model = finalize(params, state["embed_apply"], var_state, cfg,
                     xtr[:n_built], unit=state["unit"], mode="icq")
    idx = make_index("two-step", model.codes, model.C, model.structure,
                     topk=50, backend="jnp")
    idx = idx.add(model.embed(xtr[n_built:]), icm_iters=cfg.icm_iters)
    assert idx.codes.shape[0] == xtr.shape[0]
    print(f"index: built n={n_built}, +{args.hold_out} via Index.add "
          f"-> n={idx.codes.shape[0]} (no retrain)")

    emb_q = model.embed(xte)
    r2 = idx.search(emb_q)
    r1 = adc_search(emb_q, idx.codes, model.C, 50)
    print(f"two-step MAP={float(mean_average_precision(r2.indices, ytr, yte)):.4f} "
          f"ops={float(r2.avg_ops):.2f} | "
          f"adc MAP={float(mean_average_precision(r1.indices, ytr, yte)):.4f} "
          f"ops={float(r1.avg_ops):.2f}")

    if args.save_artifacts:
        path = Artifacts(config=api_cfg, model=model,
                         index=idx).save(args.save_artifacts)
        print(f"artifacts -> {path} (serve with launch/serve.py "
              "--load-artifacts)")


if __name__ == "__main__":
    main()
