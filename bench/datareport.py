"""Is the generated data SIFT-like, and not degenerate?  For each
candidate generator setting, draw a configuration's data set and report
the numbers its parameters are calibrated on:

  pq_recall_at_{1,10,100}   1-recall@R of 64-bit product quantization
                            (K subspaces of d/K dimensions, m centroids
                            each, exhaustive ADC): the share of queries
                            whose exact nearest neighbour is among the R
                            rows nearest by ADC, the figure Jegou,
                            Douze and Schmid (TPAMI 2011) publish for
                            64-bit PQ on SIFT1M;
  lid_mle                   the maximum-likelihood local intrinsic
                            dimensionality over the 100 exact neighbours;

and, with ``--icq``, the cell's ICQ fit on the same rows: the eq. 2 pass
rate, the two-step recall@10 and the exact-ADC recall@10 and 1-recall@R.

    python3 bench/datareport.py --config sift1m-icq64-twostep \\
        --grid grid.json --icq --out chiprun_out/datareport.jsonl

``--grid`` is a JSON list of generator overrides (default: the
configuration's own setting); ``--scale`` multiplies the row counts and
the number of clusters, so rows per cluster stay as at full size.  Runs
anywhere JAX runs; at full size it wants the chip.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RS = (1, 10, 100)


def _pq(learn, base, queries, *, n_sub: int, m: int, key, iters: int = 25):
    """ADC top-100 ids of ``queries`` under product quantization fitted
    on ``learn`` (Lloyd's k-means per subspace, HIGHEST precision)."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST

    @functools.partial(jax.jit, static_argnames=("m", "iters"))
    def kmeans(key, x, *, m, iters):
        c0 = x[jax.random.choice(key, x.shape[0], (m,), replace=False)]

        def body(c, _):
            s = jnp.sum(c * c, -1)[None] - 2.0 * jnp.dot(x, c.T, precision=hi)
            a = jnp.argmin(s, -1)
            tot = jax.ops.segment_sum(x, a, m)
            cnt = jax.ops.segment_sum(jnp.ones(x.shape[0]), a, m)
            return jnp.where(cnt[:, None] > 0, tot / jnp.maximum(cnt, 1.0)
                             [:, None], c), None

        return jax.lax.scan(body, c0, None, length=iters)[0]

    @jax.jit
    def encode(x, c):
        def blk(xb):
            s = jnp.sum(c * c, -1)[None] - 2.0 * jnp.dot(xb, c.T,
                                                          precision=hi)
            return jnp.argmin(s, -1).astype(jnp.int32)
        n = x.shape[0]
        xp = jnp.pad(x, ((0, (-n) % 65536), (0, 0)))
        return jax.lax.map(blk, xp.reshape(-1, 65536, x.shape[1])
                           ).reshape(-1)[:n]

    @jax.jit
    def adc_top(qs, cbs, codes):
        def blk(qb):
            d = 0.0
            for k in range(cbs.shape[0]):
                sub = qb[:, k * w:(k + 1) * w]
                lut = (jnp.sum(cbs[k] ** 2, -1)[None]
                       - 2.0 * jnp.dot(sub, cbs[k].T, precision=hi))
                d = d + jnp.take(lut, codes[:, k], axis=1)
            return jax.lax.top_k(-d, 100)[1]
        nq = qs.shape[0]
        qp = jnp.pad(qs, ((0, (-nq) % 50), (0, 0)))
        return jax.lax.map(blk, qp.reshape(-1, 50, qs.shape[1])
                           ).reshape(-1, 100)[:nq]

    d = learn.shape[1]
    w = d // n_sub
    cbs, codes = [], []
    for k in range(n_sub):
        c = kmeans(jax.random.fold_in(key, k), learn[:, k * w:(k + 1) * w],
                   m=m, iters=iters)
        cbs.append(c)
        codes.append(encode(base[:, k * w:(k + 1) * w], c))
    return adc_top(queries, jnp.stack(cbs), jnp.stack(codes, axis=1))


def _at_r(top, nn) -> dict:
    import numpy as np

    top, nn = np.asarray(top), np.asarray(nn)
    return {r: float(np.mean(np.any(top[:, :r] == nn[:, None], axis=1)))
            for r in RS}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="sift1m-icq64-twostep")
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=None,
                    help="data seed (default: the configuration's)")
    ap.add_argument("--queries", type=int, default=1000)
    ap.add_argument("--grid", default=None)
    ap.add_argument("--icq", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    import numpy as np

    from bench import cells, gen, reference, run, truth

    if jax.default_backend() == "tpu":
        run.enable_compile_cache()
    cfg0 = cells.load_config(args.config)
    seed = cfg0["assumed"]["data_seed"] if args.seed is None else args.seed
    grid = [{}]
    if args.grid:
        with open(args.grid) as f:
            grid = json.load(f)
    sh = cfg0["shapes"]
    n_learn = round(sh["n_learn"] * args.scale)
    n_base = round(sh["n_base"] * args.scale)
    nq, k = args.queries, sh["k"]
    tr = cfg0["icq"]["train"]
    lines = []
    for over in grid:
        params = dict(cfg0["assumed"]["generator"], **over)
        params["clusters"] = max(1, round(params["clusters"] * args.scale))
        learn, base, queries = gen.make_parts(seed, (n_learn, n_base, nq),
                                              sh["d"], params)
        gt, d2 = truth.exact_neighbours(queries, base, 100)
        gt, d2 = np.asarray(gt), np.asarray(d2, np.float64)
        dist = np.sqrt(np.maximum(d2, 1e-12))
        lid = -1.0 / np.mean(np.log(dist[:, :-1] / dist[:, -1:]), axis=1)
        top = _pq(learn, base, queries, n_sub=tr["num_codebooks"],
                  m=tr["codebook_size"], key=gen.seed_key(seed + 7))
        line = {"generator": params, "n_base": n_base, "queries": nq,
                "nn_dist_median": float(np.median(dist[:, 0])),
                "lid_mle_median": float(np.median(lid)),
                **{f"pq_recall_at_{r}": v
                   for r, v in _at_r(top, gt[:, 0]).items()},
                "pq_recall_10_at_10": truth.recall_at_k(
                    np.asarray(top)[:, :k], gt[:, :k], k)}
        if args.icq:
            cfg = json.loads(json.dumps(cfg0))
            cfg["assumed"]["generator"] = params
            if jax.default_backend() == "cpu":
                cfg["icq"]["serve"]["backend"] = "jnp"
                cfg["icq"]["encode"]["backend"] = "jnp"
            searcher = run.build(cfg, learn, base)
            model = cells.reference_model(searcher, cfg)
            model["sigma"] = reference.margin_sigma(
                reference.learn_variance(learn), model["C"], model["fast"])
            codes = np.asarray(searcher.index.codes)
            q = queries[:256]
            ref = reference.search(q, codes, model, topk=k)
            adc = reference.search(queries, codes,
                                   dict(model, sigma=np.float32(np.inf)),
                                   topk=100)
            line.update({
                "eq2_pass_rate": float(np.mean(ref["passed"]
                                               / ref["scanned"])),
                "two_step_recall_at_10": truth.recall_at_k(
                    ref["ids"], gt[:256, :k], k),
                "adc_recall_at_10": truth.recall_at_k(adc["ids"][:, :k],
                                                      gt[:, :k], k),
                **{f"icq_recall_at_{r}": v
                   for r, v in _at_r(adc["ids"], gt[:, 0]).items()},
                "sigma": float(model["sigma"])})
            del searcher
        lines.append(line)
        print(json.dumps(line), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            f.writelines(json.dumps(x) + "\n" for x in lines)
    return 0


if __name__ == "__main__":
    sys.exit(main())
