"""Stable names for the device stages of the search: the lowered
program's HLO ``op_name`` metadata carries the ``jax.named_scope`` of
each stage its index kind and backend run.  The Pallas kernels merge
their top-k in the kernel, so the two-step Pallas path has no ``merge``
outside it; the IVF Pallas path maps slab positions to ids there."""
import dataclasses
import re

import jax
import numpy as np
import pytest

from repro.api import ICQConfig, icq_session

STAGES = {
    ("two-step", "jnp"): {"lut_build", "crude", "threshold", "refine",
                          "merge"},
    ("two-step", "pallas"): {"lut_build", "crude", "threshold", "refine"},
    ("ivf", "jnp"): {"lut_build", "probe", "ivf_gather", "crude",
                     "threshold", "refine", "merge"},
    ("ivf", "pallas"): {"lut_build", "probe", "ivf_gather", "crude",
                        "threshold", "refine", "merge"},
}
ALL = set().union(*STAGES.values())


@pytest.fixture(scope="module")
def indexes():
    rng = np.random.default_rng(0)
    X = rng.standard_normal((512, 16)).astype(np.float32)
    out = {}
    for kind in ("two-step", "ivf"):
        sess = icq_session(ICQConfig().with_overrides(
            {"train.d": 16, "train.num_codebooks": 4,
             "train.codebook_size": 16, "train.epochs": 1,
             "index.kind": kind, "index.n_lists": 8, "index.n_probe": 2,
             "serve.topk": 5}))
        sess.fit(X[:256], key=jax.random.PRNGKey(0))
        out[kind] = sess.index(X).index
    return out


@pytest.mark.parametrize("kind,backend", sorted(STAGES))
def test_lowered_search_names_each_stage(indexes, kind, backend):
    idx = dataclasses.replace(indexes[kind], backend=backend)
    q = np.zeros((8, 16), np.float32)
    text = jax.jit(lambda x: idx.search(x)).lower(q).as_text(
        dialect="hlo", debug_info=True)
    names = re.findall(r'op_name="([^"]*)"', text)
    found = {s for s in ALL if any(f"/{s}/" in n for n in names)}
    assert found == STAGES[(kind, backend)]
