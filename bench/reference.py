"""The plain reference: ICM encoding, the eq. 11 margin, the IVF lists and
the two-step and IVF searches written out in ``jax.numpy`` from their
definitions, with no kernels, tiling or batching tricks.  It imports
nothing of the program.  The ICM encoding and the flat search read the
rows a block at a time (``bench/blocks.py``), so that a check over ten
million rows fits one chip; the blocks change no result.

It takes from the program's fit only what is learned by training the
benchmark does not repeat: the codebooks ``C`` (K, m, d), the
fast-codebook set, and for IVF the coarse centroids.  The stored codes
are held to the codebooks (``icm_codes``) and the program's inverted
lists to the centroids (``lists_misfiled``); the margin, the lists the
search walks, the tables, sums, thresholds, probes and top-k are
computed here, from the benchmark's own learn and base rows.

Semantics (paper eqs. 1-2 and 11 as the index states them):

  LUT        T[q, k, j] = ||c_kj||^2 - 2 <q, c_kj>   (HIGHEST precision)
  crude      sum of T over the fast codebooks of a row's codes
  slow       sum of T over the other codebooks
  margin     eq. 11: sigma = sum of the learn rows' per-dimension
             variances outside psi, psi being the coordinates the fast
             codebooks span (the fit projects each codebook onto its
             side of the split)
  threshold  the crude top-k candidates are ranked by crude + slow; the
             furthest one's crude value plus sigma is the threshold
  refine     rows whose crude value is below the threshold are ranked
             by crude + slow; the top-k are the answer
  ties       lower position first (row id; slab position for IVF)
  IVF        a row's score against a centroid is ``||c||^2 - 2 x.c`` at
             the precision of the platform's default f32 product, which
             the program's coarse assignment and probe run at: on the TPU
             one bfloat16 pass (both operands rounded to bfloat16, exact
             products, f32 sums), elsewhere f32.  Every base row is filed
             in the list of its lowest score, lists in ascending row
             order; a query probes the n_probe lists of lowest score; the
             candidates are the probed lists' rows in probe order.
  band       another order of the same f32 sums moves a score by up to
             the ``tol`` of ``coarse_scores``: a row whose two best
             lists lie within it is filed ambiguously, and a list within
             it of the probe's cut may be probed or not.  The search runs over the lists every
             such order probes, without the ambiguous rows; an answered
             row has to lie in a list some such order probes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import blocks

HIGHEST = jax.lax.Precision.HIGHEST
ROW_BLOCK = 131072          # rows per block of the flat search's two passes
# one f32 add rounds by at most 2**-24 of its result (nearest) or 2**-23
# (toward zero); a sum of d terms in any order lies within d times that
# of the sum of absolute terms
F32_ADD_REL = 2.0 ** -23


def _sq_norms(C, precision):
    return jnp.einsum("kmd,kmd->km", C, C, precision=precision)


@functools.partial(jax.jit, static_argnames=("iters", "chunk", "precision"))
def icm_codes(x, C, *, iters: int, chunk: int = 8192, precision=HIGHEST):
    """ICM codes of rows ``x`` (n, d): the independent nearest codeword
    per codebook, then ``iters`` sweeps re-choosing codebook k with the
    others fixed, k = 0..K-1.  Chunks of ``chunk`` rows are read in
    place; only the last, short one is padded.  Returns (n, K) int32."""
    K = C.shape[0]
    sq = _sq_norms(C, HIGHEST)
    n, d = x.shape
    full, rem = divmod(n, chunk)
    tail = jnp.pad(x[full * chunk:], ((0, (chunk - rem) % chunk), (0, 0)))

    def block(j):
        if not full:
            xb = tail
        else:
            xb = jax.lax.dynamic_slice_in_dim(
                x, jnp.minimum(j, full - 1) * chunk, chunk)
            if rem:
                xb = jnp.where(j < full, xb, tail)
        scores = (-2.0 * jnp.einsum("nd,kmd->knm", xb, C,
                                    precision=precision) + sq[:, None, :])
        codes = [jnp.argmin(scores[k], axis=-1) for k in range(K)]
        recon = 0
        for k in range(K):
            recon = recon + C[k][codes[k]]
        for _ in range(iters):
            for k in range(K):
                r = recon - C[k][codes[k]]
                s = sq[k][None, :] - 2.0 * jnp.dot(xb - r, C[k].T,
                                                   precision=precision)
                codes[k] = jnp.argmin(s, axis=-1)
                recon = r + C[k][codes[k]]
        return jnp.stack(codes, axis=1).astype(jnp.int32)

    out = jax.lax.map(block, jnp.arange(full + (rem > 0)))
    return out.reshape(-1, K)[:n]


@jax.jit
def learn_variance(x):
    """Per-dimension variance of rows ``x`` (n, d), two-pass."""
    mean = jnp.mean(x, axis=0)
    return jnp.mean(jnp.square(x - mean), axis=0)


def margin_sigma(learn_var, C, fast, scale: float = 1.0) -> np.float32:
    """Eq. 11: ``scale`` times the variance outside psi, psi being the
    coordinates on which some fast codebook is not zero."""
    C = np.asarray(C)
    psi = np.any(C[np.asarray(fast, bool)] != 0.0, axis=(0, 1))
    return np.float32(scale * np.sum(np.asarray(learn_var, np.float64)[~psi]))


# --------------------------------------------------------------- IVF ---

def coarse_scores(x, centroids, rounded: bool):
    """Scores ``||c||^2 - 2 x.c`` of rows ``x`` (n, d) against the
    centroids (L, d), with both operands of the product rounded to
    bfloat16 where ``rounded`` (one bfloat16 pass), and ``tol``: how far
    another order of the same f32 sums can move each score.  Returns
    (score, tol), each (n, L)."""
    c = centroids
    if rounded:
        x = x.astype(jnp.bfloat16).astype(jnp.float32)
        c = c.astype(jnp.bfloat16).astype(jnp.float32)
    csq = jnp.sum(jnp.square(centroids), axis=-1)[None, :]
    score = csq - 2.0 * jnp.dot(x, c.T, precision=HIGHEST)
    absdot = jnp.dot(jnp.abs(x), jnp.abs(c).T, precision=HIGHEST)
    tol = 2.0 * F32_ADD_REL * (x.shape[1] * (2.0 * absdot + csq)
                               + jnp.abs(score))
    return score, tol


def _near(score, tol):
    """The lists some order of the sums puts lowest: (n, L) mask."""
    return score - tol <= jnp.min(score + tol, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("rounded", "chunk"))
def _filing(x, centroids, filed, *, rounded: bool, chunk: int = 8192):
    """Per row: the list of lowest score, whether another list lies
    within the band of it, and whether the program's list ``filed``
    (-1: none) is one of those within the band."""
    n, d = x.shape
    pad = (-n) % chunk
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    fp = jnp.pad(filed, (0, pad), constant_values=-1)

    def block(args):
        xb, fb = args
        score, tol = coarse_scores(xb, centroids, rounded)
        near = _near(score, tol)
        hit = jnp.take_along_axis(near, jnp.maximum(fb, 0)[:, None], 1)
        return (jnp.argmin(score, axis=1).astype(jnp.int32),
                jnp.sum(near, axis=1) > 1, (fb >= 0) & hit[:, 0])

    best, ambiguous, ok = jax.lax.map(block, (xp.reshape(-1, chunk, d),
                                              fp.reshape(-1, chunk)))
    return (best.reshape(-1)[:n], ambiguous.reshape(-1)[:n],
            ok.reshape(-1)[:n])


def program_filing(lists, n: int) -> np.ndarray:
    """The list each row 0..n-1 sits in under the program's padded
    (n_lists, max_len) id slab: -1 where a row is absent or in more than
    one place."""
    lists = np.asarray(lists)
    flat = lists.reshape(-1)
    valid = (flat >= 0) & (flat < n)
    rows = flat[valid].astype(np.int64)
    lst = np.repeat(np.arange(lists.shape[0]), lists.shape[1])[valid]
    count = np.bincount(rows, minlength=n)[:n]
    filed = np.full(n, -1, np.int32)
    filed[rows] = lst
    filed[count != 1] = -1
    return filed


def ivf_lists(base, centroids, program_lists, *, rounded: bool):
    """The reference's inverted lists over ``base``, which rows are filed
    ambiguously, and the share of rows the program's lists misfile:
    absent, in more than one list, or in a list outside the band of the
    lowest score.  Returns (lists (n_lists, max_len) int32 with -1 pad,
    ambiguous (n,) bool, misfiled share)."""
    n = base.shape[0]
    n_lists = centroids.shape[0]
    filed = program_filing(program_lists, n)
    best, ambiguous, ok = _filing(jnp.asarray(base), jnp.asarray(centroids),
                                  jnp.asarray(filed), rounded=rounded)
    best = np.asarray(best)
    misfiled = float(1.0 - np.mean(np.asarray(ok)))
    order = np.argsort(best, kind="stable")
    lens = np.bincount(best, minlength=n_lists)
    lists = np.full((n_lists, max(int(lens.max()), 1)), -1, np.int32)
    starts = np.concatenate([[0], np.cumsum(lens)[:-1]])
    for l in range(n_lists):
        lists[l, :lens[l]] = order[starts[l]:starts[l] + lens[l]]
    return lists, np.asarray(ambiguous), misfiled


def _probe_band(score, tol, n_probe: int):
    """Per query (nq, L): the lists every order of the sums probes, and
    the lists some order probes."""
    if n_probe >= score.shape[1]:
        every = jnp.ones(score.shape, bool)
        return every, every
    # l is probed by every order when even at its highest it lies below
    # the (n_probe+1)-th lowest of all lists' lowest; by some order when
    # at its lowest it reaches the n_probe-th lowest of all lists' highest
    cut_lo = -jax.lax.top_k(-(score - tol), n_probe + 1)[0][:, -1:]
    cut_hi = -jax.lax.top_k(-(score + tol), n_probe)[0][:, -1:]
    return score + tol < cut_lo, score - tol <= cut_hi


# ------------------------------------------------------------ search ---

def _luts(qs, C):
    return (_sq_norms(C, HIGHEST)[None]
            - 2.0 * jnp.einsum("qd,kmd->qkm", qs, C, precision=HIGHEST))


def _sums(T, codes, fast):
    """(crude, slow) of rows ``codes`` (..., K) under tables T (q, K, m):
    codes (n, K) gives (q, n); codes (q, t, K) gives (q, t)."""
    crude = slow = 0.0
    for k in range(T.shape[1]):
        if codes.ndim == 2:
            v = jnp.take(T[:, k, :], codes[:, k], axis=1)
        else:
            v = jnp.take_along_axis(T[:, k, :], codes[:, :, k], axis=1)
        crude = crude + jnp.where(fast[k], v, 0.0)
        slow = slow + jnp.where(fast[k], 0.0, v)
    return crude, slow


def _two_step(crude, slow, sigma, topk: int):
    """Eq. 2 bootstrap + refine over one candidate axis (+inf crude marks
    an absent candidate).  Returns (positions, distances, passed mask)."""
    neg_c, cand = jax.lax.top_k(-crude, topk)
    cand_vals = -neg_c
    ok = jnp.isfinite(cand_vals)
    full_cand = cand_vals + jnp.take_along_axis(slow, cand, axis=1)
    far = jnp.argmax(jnp.where(ok, full_cand, -jnp.inf), axis=1)
    thr = jnp.take_along_axis(cand_vals, far[:, None], axis=1) + sigma
    passed = crude < thr
    ranked = jnp.where(passed, crude + slow, jnp.inf)
    neg, pos = jax.lax.top_k(-ranked, topk)
    return pos, -neg, passed


def _lut_range(T):
    """Per-query span of possible distances: sum over codebooks of
    (largest - smallest table entry).  The scale the distance gaps are
    measured against."""
    return jnp.sum(jnp.max(T, axis=2) - jnp.min(T, axis=2), axis=1)


def _of_answers(T, codes, fast, answers):
    """Distance of each answered row (q, t) under the query's tables;
    +inf where the answer is not a row."""
    n = codes.shape[0]
    valid = (answers >= 0) & (answers < n)
    crude, slow = _sums(T, jnp.take(codes, jnp.where(valid, answers, 0),
                                    axis=0), fast)
    return jnp.where(valid, crude + slow, jnp.inf)


@functools.partial(jax.jit, static_argnames=("topk", "rows"))
def two_step_block(qs, codes, C, fast, sigma, answers, *, topk: int,
                   rows: int = ROW_BLOCK):
    """Flat two-step over all rows for one query block, in two passes
    over blocks of ``rows`` rows: the crude top-k and from it the eq. 2
    threshold, then refine with a running top-k.  Returns (ids, dists,
    LUT range, passed per query, candidates per query, distance of each
    given answer, whether each given answer is a row the search can
    reach) and, for the block, the distinct rows scanned and passed."""
    T = _luts(qs, C)
    n = codes.shape[0]
    rows = min(rows, n)
    n_blocks = -(-n // rows)

    def sums(j):
        start, ids, new = blocks.row_block(j, n, rows)
        crude, slow = _sums(
            T, jax.lax.dynamic_slice_in_dim(codes, start, rows), fast)
        return jnp.where(new, crude, jnp.inf), slow, ids

    def crude_block(j):
        crude, _, ids = sums(j)
        return crude, ids, ()

    (cand_vals, cand), _ = blocks.running_topk(crude_block, n_blocks, topk)
    ok = jnp.isfinite(cand_vals)
    full_cand = cand_vals + _sums(T, jnp.take(codes, cand, axis=0), fast)[1]
    far = jnp.argmax(jnp.where(ok, full_cand, -jnp.inf), axis=1)
    thr = jnp.take_along_axis(cand_vals, far[:, None], axis=1) + sigma

    def refine_block(j):
        crude, slow, ids = sums(j)
        passed = crude < thr
        return (jnp.where(passed, crude + slow, jnp.inf), ids,
                (jnp.sum(passed, axis=1), jnp.sum(jnp.any(passed, axis=0))))

    (dist, ids), (n_passed, rows_passed) = blocks.running_topk(
        refine_block, n_blocks, topk)
    n_cand = jnp.full(qs.shape[:1], n, jnp.int32)
    return (ids, dist, _lut_range(T), n_passed, n_cand,
            _of_answers(T, codes, fast, answers),
            (answers >= 0) & (answers < n), jnp.int32(n), rows_passed)


@functools.partial(jax.jit, static_argnames=("topk", "n_probe", "rounded"))
def ivf_block(qs, codes, C, fast, sigma, answers, centroids, lists,
              ambiguous, base, *, topk: int, n_probe: int, rounded: bool):
    """IVF two-step for one query block over the slab of the lists every
    order of the sums probes, less the rows filed ambiguously; a given
    answer can be reached where some order files it in a list that some
    order probes."""
    nq = qs.shape[0]
    T = _luts(qs, C)
    score, tol = coarse_scores(qs, centroids, rounded)
    every, some = _probe_band(score, tol, n_probe)
    _, probes = jax.lax.top_k(-score, n_probe)
    cand = jnp.where(jnp.take_along_axis(every, probes, 1)[:, :, None],
                     lists[probes], -1).reshape(nq, -1)
    valid = cand >= 0
    safe = jnp.where(valid, cand, 0)
    valid = valid & ~ambiguous[safe]
    crude, slow = _sums(T, jnp.take(codes, safe, axis=0), fast)
    crude = jnp.where(valid, crude, jnp.inf)
    pos, dist, passed = _two_step(crude, slow, sigma, topk)
    ids = jnp.take_along_axis(safe, pos, axis=1)
    n = codes.shape[0]
    answered = (answers >= 0) & (answers < n)
    rows = jnp.take(base, jnp.where(answered, answers, 0).reshape(-1), axis=0)
    near = _near(*coarse_scores(rows, centroids, rounded))
    reach = jnp.any(near.reshape(nq, answers.shape[1], -1) & some[:, None],
                    axis=2) & answered
    scanned = jnp.zeros(n, bool).at[safe].max(valid)
    kept = jnp.zeros(n, bool).at[safe].max(passed)
    return (ids, dist, _lut_range(T), jnp.sum(passed, axis=1),
            jnp.sum(valid, axis=1), _of_answers(T, codes, fast, answers),
            reach, jnp.sum(scanned), jnp.sum(kept))


def search(queries, codes, model: dict, *, topk: int, answers=None,
           block: int = 64):
    """Reference answers for ``queries`` (nq, d) over ``codes`` (n, K),
    in blocks of ``block`` queries.  ``model`` holds C, fast, sigma and,
    for IVF, centroids, lists, ambiguous, base, rounded and n_probe.
    ``answers`` (nq, t), the program's ids, get their distances under the
    same tables.  Returns numpy arrays: per query ``ids``, ``dists``,
    ``lut_range``, ``passed``, ``scanned``, ``answer_dists`` and
    ``reachable``; per block ``rows_scanned`` and ``rows_passed``
    (distinct rows)."""
    q = jnp.asarray(queries, jnp.float32)
    if answers is None:
        answers = np.full((q.shape[0], topk), -1, np.int32)
    answers = jnp.asarray(np.asarray(answers), jnp.int32)
    codes = jnp.asarray(codes).astype(jnp.int32)
    args = (codes, model["C"], model["fast"], model["sigma"])
    if "centroids" in model:
        ivf = (jnp.asarray(model["centroids"]), jnp.asarray(model["lists"]),
               jnp.asarray(model["ambiguous"]), jnp.asarray(model["base"]))
    outs = []
    for s in range(0, q.shape[0], block):
        qb, ab = q[s:s + block], answers[s:s + block]
        if "centroids" in model:
            o = ivf_block(qb, *args, ab, *ivf, topk=topk,
                          n_probe=int(model["n_probe"]),
                          rounded=bool(model["rounded"]))
        else:
            o = two_step_block(qb, *args, ab, topk=topk)
        outs.append([np.asarray(a) for a in o])
    names = ("ids", "dists", "lut_range", "passed", "scanned",
             "answer_dists", "reachable", "rows_scanned", "rows_passed")
    return {n: (np.concatenate(parts, axis=0) if parts[0].ndim
                else np.asarray(parts))
            for n, parts in zip(names, zip(*outs))}
