"""Each set-up and check program that runs over the base rows holds one
block of rows at a time, and gives what its whole form gives: the data
draw the same bits, the exact neighbours, the ICM codes and the flat
reference search the same ids, distances and counts.  The whole forms
below are the programs as they were before they were blocked.

Apart from the draw, the data are small whole numbers: every product and
sum is then exact, so no order of summation a backend picks for one
shape and not another can tell the forms apart, and equal rows and equal
distances are common, which puts the tie rules to the test."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tinycell import ROOT  # noqa: F401  (puts the benchmark on sys.path)

from bench import gen, reference, truth, verdict

HIGHEST = jax.lax.Precision.HIGHEST
PARAMS = {"clusters": 64, "size_sigma": 0.5, "mean": 20.0, "center_std": 40.0,
          "center_decay": 0.5, "rank": 8, "within_std": 40.0,
          "within_decay": 0.7, "noise_std": 1.0}


# ------------------------------------------------------- whole forms ---

@functools.partial(jax.jit, static_argnames=("n", "d", "p"))
def _whole_rows(key, *, n, d, p):
    q = dict(p)
    nc, r = int(q["clusters"]), int(q["rank"])
    k_c, k_g, k_b, k_w, k_rows = jax.random.split(key, 5)
    center_scale = q["center_std"] * jnp.arange(
        1, d + 1, dtype=jnp.float32) ** -q["center_decay"]
    mix = jax.random.normal(k_g, (d, d), jnp.float32) / jnp.sqrt(d)
    centers = q["mean"] + (jax.random.normal(k_c, (nc, d), jnp.float32)
                           * center_scale) @ mix
    bases = jax.random.normal(k_b, (nc, d, r), jnp.float32) / jnp.sqrt(d)
    within = q["within_std"] * jnp.arange(
        1, r + 1, dtype=jnp.float32) ** -q["within_decay"]
    logits = q["size_sigma"] * jax.random.normal(k_w, (nc,), jnp.float32)
    B = gen.BLOCK_ROWS

    def block(i):
        k_id, k_u, k_e = jax.random.split(jax.random.fold_in(k_rows, i), 3)
        cid = jax.random.categorical(k_id, logits, shape=(B,))
        u = jax.random.normal(k_u, (B, r), jnp.float32) * within
        e = jax.random.normal(k_e, (B, d), jnp.float32)
        x = (centers[cid] + jnp.einsum("ndr,nr->nd", bases[cid], u,
                                       precision=HIGHEST)
             + q["noise_std"] * e)
        return jnp.maximum(x, 0.0)

    out = jax.lax.map(block, jnp.arange(-(-n // B)))
    return out.reshape(-1, d)[:n]


@functools.partial(jax.jit, static_argnames=("k",))
def _whole_neighbours(queries, base, *, k):
    xsq = jnp.einsum("nd,nd->n", base, base, precision=HIGHEST)
    d2 = (jnp.einsum("qd,qd->q", queries, queries,
                     precision=HIGHEST)[:, None]
          - 2.0 * jnp.dot(queries, base.T, precision=HIGHEST) + xsq[None, :])
    neg, ids = jax.lax.top_k(-d2, k)
    return ids, -neg


@functools.partial(jax.jit, static_argnames=("iters",))
def _whole_icm(x, C, *, iters):
    K = C.shape[0]
    sq = jnp.einsum("kmd,kmd->km", C, C, precision=HIGHEST)
    scores = (-2.0 * jnp.einsum("nd,kmd->knm", x, C, precision=HIGHEST)
              + sq[:, None, :])
    codes = [jnp.argmin(scores[k], axis=-1) for k in range(K)]
    recon = sum(C[k][codes[k]] for k in range(K))
    for _ in range(iters):
        for k in range(K):
            r = recon - C[k][codes[k]]
            s = sq[k][None, :] - 2.0 * jnp.dot(x - r, C[k].T,
                                               precision=HIGHEST)
            codes[k] = jnp.argmin(s, axis=-1)
            recon = r + C[k][codes[k]]
    return jnp.stack(codes, axis=1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("topk",))
def _whole_two_step(qs, codes, C, fast, sigma, answers, *, topk):
    T = reference._luts(qs, C)
    crude, slow = reference._sums(T, codes, fast)
    ids, dist, passed = reference._two_step(crude, slow, sigma, topk)
    n = codes.shape[0]
    return (ids, dist, reference._lut_range(T), jnp.sum(passed, axis=1),
            jnp.full(qs.shape[:1], n, jnp.int32),
            reference._of_answers(T, codes, fast, answers),
            (answers >= 0) & (answers < n), jnp.int32(n),
            jnp.sum(jnp.any(passed, axis=0)))


# ------------------------------------------------------------- data ---

def _ints(rng, shape, lo, hi, dup=()):
    """Whole numbers in [lo, hi); rows ``b`` copy rows ``a`` for each
    (a, b) slice pair in ``dup``."""
    x = rng.integers(lo, hi, size=shape).astype(np.float32)
    for a, b in dup:
        x[b] = x[a]
    return x


N, D = 1000, 8
DUP = ((slice(0, 40), slice(500, 540)), (slice(100, 110), slice(990, 1000)))


# ------------------------------------------------------ the draw ---

@pytest.mark.parametrize("start,n", [
    (0, 3 * 16384 + 5000),           # several blocks, a short last one
    (20000, 30000),                  # starts and ends inside a block
    (16380, 100),                    # straddles one block boundary
    (0, 100),                        # shorter than a block
    (16384, 16384),                  # exactly one block
])
def test_a_range_of_the_draw_is_the_same_bits_as_the_whole(start, n):
    p = tuple(sorted((k, float(v)) for k, v in PARAMS.items()))
    whole = np.asarray(_whole_rows(gen.seed_key(5), n=start + n, d=16, p=p))
    part = np.asarray(gen.make_parts(5, (start, n), 16, PARAMS)[1])
    np.testing.assert_array_equal(part, whole[start:])


# ------------------------------------------------ exact neighbours ---

@pytest.mark.parametrize("rows", [64, 100, 128, 333, 1000, 4096])
def test_exact_neighbours_by_row_blocks_equal_the_whole(rows):
    rng = np.random.default_rng(rows)
    base = _ints(rng, (N, D), -4, 5, DUP)
    q = np.concatenate([base[[3, 105, 520]], _ints(rng, (34, D), -4, 5)])
    want_ids, want_d2 = _whole_neighbours(q, base, k=12)
    assert (np.diff(np.asarray(want_d2), axis=1) == 0).any()   # ties
    ids, d2 = truth.exact_neighbours(q, base, 12, block=16, rows=rows)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(want_ids))
    np.testing.assert_array_equal(np.asarray(d2), np.asarray(want_d2))


# ----------------------------------------------------------- ICM ---

@pytest.mark.parametrize("chunk", [100, 128, 333, 1000, 2048])
def test_icm_codes_by_chunks_equal_the_whole(chunk):
    rng = np.random.default_rng(chunk)
    x = _ints(rng, (N, D), -6, 7, DUP)
    C = _ints(rng, (4, 16, D), -3, 4)
    want = np.asarray(_whole_icm(x, C, iters=2))
    got = np.asarray(reference.icm_codes(x, C, iters=2, chunk=chunk))
    np.testing.assert_array_equal(got, want)


# ------------------------------------------------ flat reference ---

NAMES = ("ids", "dists", "lut_range", "passed", "scanned", "answer_dists",
         "reachable", "rows_scanned", "rows_passed")


@pytest.mark.parametrize("sigma", [0.0, 6.0, np.inf])
@pytest.mark.parametrize("rows", [64, 100, 1000, 4096])
def test_flat_search_by_row_blocks_equals_the_whole(rows, sigma):
    rng = np.random.default_rng(rows)
    K, m = 4, 16
    C = _ints(rng, (K, m, D), -3, 4)
    codes = rng.integers(0, m, size=(N, K)).astype(np.int32)
    for a, b in DUP:
        codes[b] = codes[a]
    q = _ints(rng, (64, D), -6, 7)
    fast = np.array([True, False, True, False])
    head = (jnp.asarray(q), jnp.asarray(codes), C, fast, np.float32(sigma))
    # answers as a program gives them: the search's own, some slots of
    # them altered
    first = np.asarray(_whole_two_step(
        *head, jnp.full((64, 10), -1, jnp.int32), topk=10)[0])
    alter = rng.random((64, 10)) < 0.2
    answers = np.where(alter, rng.integers(-1, N, size=(64, 10)),
                       first).astype(np.int32)
    want = dict(zip(NAMES, map(np.asarray, _whole_two_step(
        *head, jnp.asarray(answers), topk=10))))
    got = dict(zip(NAMES, map(np.asarray, reference.two_step_block(
        *head, jnp.asarray(answers), topk=10, rows=rows))))
    for name in NAMES:
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)
    assert (np.diff(want["dists"], axis=1) == 0).any()          # ties
    assert 0 < want["rows_passed"] <= N
    missed = [verdict.ids_missed(answers, r["answer_dists"], r["dists"],
                                 r["lut_range"], r["reachable"])
              for r in (want, got)]
    gap = [verdict.dist_gap(want["dists"], r["answer_dists"], r["lut_range"])
           for r in (want, got)]
    assert missed[0] == missed[1] and gap[0] == gap[1]
