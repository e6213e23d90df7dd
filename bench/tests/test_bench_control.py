"""The comparison that decides ``correct``, driven through the whole
harness on a CPU-sized cell: sound runs pass, the lower-precision
control (the program's own int8 crude tables) fails, and so does each
fault planted under the timed path."""
import numpy as np
import pytest
from tinycell import run_tiny

CELLS = ("sift1m-twostep.batch64", "sift1m-ivf1024.batch64",
         "sift1m-twostep.poisson1")


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    out = run_tiny(cell)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("cell", CELLS)
def test_int8_control_is_not_correct(cell):
    out = run_tiny(cell, overrides={"serve.lut_dtype": "int8"})
    assert not out["correct"]
    gap = out["checks"]["dist_gap"]
    assert gap["value"] > gap["limit"]


def _alter_answer(res, queries):
    n = 8192                                   # tiny_cell's n_base
    return res._replace(indices=(np.asarray(res.indices) + 1) % n)


def _drop_half(res, queries):
    """Answer only the first half of the batch's real rows (the serving
    loop pads flushes with zero rows); the rest get those answers."""
    ids = np.asarray(res.indices).copy()
    d = np.asarray(res.distances).copy()
    real = np.flatnonzero(np.any(np.asarray(queries) != 0, axis=1))
    h = len(real) // 2
    if h == 0:                                 # one real row: left out
        ids[real] = 0
    else:
        rest = real[h:]
        src = real[np.arange(len(rest)) % h]
        ids[rest], d[rest] = ids[src], d[src]
    return res._replace(indices=ids, distances=d)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [_alter_answer, _drop_half],
                         ids=["answer_altered", "half_batch_left_out"])
def test_fault_under_the_timed_path_is_not_correct(monkeypatch, cell, fault):
    from repro.api.serving import AnnEngine

    search = AnnEngine.search

    def broken(self, queries, *a, **kw):
        return fault(search(self, queries, *a, **kw), queries)

    monkeypatch.setattr(AnnEngine, "search", broken)
    out = run_tiny(cell)
    assert not out["correct"], out["checks"]


def _drop_last_row(buckets):
    return [b[:-1] if len(b) > 1 else b for b in buckets]


def _shift_lists(buckets):
    return buckets[1:] + buckets[:1]


@pytest.mark.parametrize("fault", [_drop_last_row, _shift_lists],
                         ids=["rows_dropped", "rows_misfiled"])
def test_ivf_lists_at_fault_are_not_correct(monkeypatch, fault):
    """The program's inverted lists are held to its centroids: lists
    that lose rows or file them under another centroid fail."""
    from repro.index import ivf

    pack = ivf._pack_buckets
    monkeypatch.setattr(ivf, "_pack_buckets",
                        lambda b, n_lists, n: pack(fault(b), n_lists, n))
    out = run_tiny("sift1m-ivf1024.batch64")
    assert not out["correct"]
    assert out["checks"]["lists_misfiled"]["value"] > 0


def test_a_probe_at_fault_is_not_correct(monkeypatch):
    """An IVF probe that takes other lists than the nearest answers rows
    no probe within rounding reaches."""
    from repro.index import ivf

    probe = ivf.coarse_probe
    monkeypatch.setattr(ivf, "coarse_probe", lambda qs, c, n_probe:
                        probe(-qs, c, n_probe))
    out = run_tiny("sift1m-ivf1024.batch64")
    assert not out["correct"]
    assert out["checks"]["ids_missed"]["value"] > \
        out["checks"]["ids_missed"]["limit"]


def test_a_margin_at_fault_is_not_correct(monkeypatch):
    """The reference takes the eq. 11 margin from its own learn rows: a
    program whose margin is nought prunes rows the reference keeps."""
    from repro.core import icq

    monkeypatch.setattr(icq, "margin_sigma", lambda lam, xi, scale=1.0:
                        0.0 * icq.jnp.sum(lam))
    out = run_tiny("sift1m-twostep.batch64")
    assert not out["correct"], out["checks"]
