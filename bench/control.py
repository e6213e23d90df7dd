#!/usr/bin/env python3
"""The readings the limits in ``limits.json`` are set from, taken on the
chip at a cell's own size, in one process:

  sound     ``run.run`` of the cell on each of ``--seeds``: the compared
            numbers of the program as the configuration states it;
  control   the same on each of ``--control-seeds`` with the program's
            own lower-precision path switched on (``serve.lut_dtype =
            int8``: int8 crude tables where the configuration states
            f32), served by whatever engine the program picks for it
            (on IVF its int8 Pallas kernel does not compile for the
            TPU and the program fails over to its jnp engine, still
            with int8 tables); and once, for the encode layer, the
            reference's ICM
            codes computed at ``Precision.HIGH`` (three bf16 passes) in
            place of the program's, against the reference at
            ``HIGHEST`` (the data set and index do not depend on the
            run's seed, so one reading is the reading of every seed).

    python3 bench/control.py --workload sift1m-twostep.batch64 \
        --seeds 1,2,3 --control-seeds 4,5,6 --seconds 3

Prints one JSON line per run and writes them all to ``--out``.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _seeds(text):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    import numpy as np

    from bench import cells, reference, run, verdict

    rows = []

    def emit(row):
        rows.append(row)
        print(json.dumps(row), flush=True)

    cell = cells.find_cell(args.workload)
    for kind, seeds, overrides in (
            ("sound", args.seeds, None),
            ("control_int8_lut", args.control_seeds,
             {"serve.lut_dtype": "int8"})):
        if not seeds:
            continue
        st = run.prepare(cell, overrides=overrides,
                         backend=None if overrides else "pallas")
        for seed in seeds:
            out = run.measure(st, seed, args.seconds, False, keep=True)
            emit({"kind": kind, "seed": seed, "backend": st["backend"],
                  "correct": out["correct"], "checks": out["checks"],
                  "log": out["_log"]})
        st["kind"].close(st["traffic"])
        C = np.asarray(st["searcher"].model.C)
        base = st["base"]
        del st
    if args.control_seeds:
        iters = int(cell["config"]["icq"]["encode"]["icm_iters"])
        exact = np.asarray(reference.icm_codes(base, C, iters=iters))
        high = np.asarray(reference.icm_codes(
            base, C, iters=iters, precision=jax.lax.Precision.HIGH))
        emit({"kind": "control_icm_high",
              "codes_mismatch": verdict.codes_mismatch(high, exact)})
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
