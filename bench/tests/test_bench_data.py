"""The benchmark's own data generator and exact ground truth."""
import numpy as np
import pytest
from tinycell import ROOT  # noqa: F401  (puts the benchmark on sys.path)

from bench import gen, truth

PARAMS = {"clusters": 8, "size_sigma": 0.5, "mean": 20.0, "center_std": 40.0,
          "center_decay": 0.5, "rank": 4, "within_std": 40.0,
          "within_decay": 0.7, "noise_std": 1.0}


def test_same_seed_same_rows():
    a = np.asarray(gen.make_rows(5, 3000, 16, PARAMS))
    b = np.asarray(gen.make_rows(5, 3000, 16, PARAMS))
    np.testing.assert_array_equal(a, b)


def test_other_seed_other_rows():
    a = np.asarray(gen.make_rows(5, 300, 16, PARAMS))
    b = np.asarray(gen.make_rows(6, 300, 16, PARAMS))
    assert not np.allclose(a, b)


@pytest.mark.parametrize("seed", [0, 2**31 - 1, 2**31 + 5, 2**40 + 3])
def test_any_whole_seed_makes_non_negative_rows(seed):
    x = np.asarray(gen.make_rows(seed, 100, 16, PARAMS))
    assert x.shape == (100, 16) and x.dtype == np.float32
    assert np.all(x >= 0) and np.isfinite(x).all() and x.max() > 0


def test_seeds_beyond_32_bits_differ():
    a = np.asarray(gen.make_rows(2**32 + 1, 50, 16, PARAMS))
    b = np.asarray(gen.make_rows(1, 50, 16, PARAMS))
    assert not np.allclose(a, b)


def test_rows_are_clustered():
    """Nearest neighbours are much closer than typical rows."""
    x = np.asarray(gen.make_rows(3, 2000, 32, dict(PARAMS, clusters=20)))
    d2 = ((x[:100, None, :] - x[None, 100:, :]) ** 2).sum(-1)
    assert np.median(np.sqrt(d2.min(1))) < 0.5 * np.median(np.sqrt(d2))


def test_split_is_disjoint_and_ordered():
    """Learn, base and query pool are consecutive ranges of one draw."""
    x = np.asarray(gen.make_rows(4, 10, 16, PARAMS))
    learn, base, q = gen.make_parts(4, (3, 5, 2), 16, PARAMS)
    assert learn.shape[0] == 3 and base.shape[0] == 5 and q.shape[0] == 2
    np.testing.assert_array_equal(np.concatenate([learn, base, q]), x)


def test_exact_neighbours_match_brute_force():
    rng = np.random.default_rng(0)
    base = rng.normal(size=(500, 8)).astype(np.float32)
    q = rng.normal(size=(37, 8)).astype(np.float32)
    ids, d2 = truth.exact_neighbours(q, base, 5, block=16)
    full = ((q[:, None, :] - base[None]) ** 2).sum(-1)
    want = np.argsort(full, axis=1)[:, :5]
    np.testing.assert_array_equal(np.asarray(ids), want)
    np.testing.assert_allclose(np.asarray(d2),
                               np.take_along_axis(full, want, 1), rtol=1e-4,
                               atol=1e-3)
