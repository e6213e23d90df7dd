"""Stable names for the device stages of the search: the lowered
program's HLO ``op_name`` metadata carries the ``jax.named_scope`` of
each stage its index kind and backend run.  The Pallas kernels merge
their top-k in the kernel, so the two-step Pallas path has no ``merge``
outside it; the IVF Pallas path maps slab positions to ids there.  A
row-sharded search names its cross-chip merges ``shard_merge``."""
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


@pytest.mark.parametrize("backend,stages", [
    ("pallas", {"lut_build", "crude", "threshold", "refine",
                "shard_merge"}),
    ("jnp", {"lut_build", "shard_merge"})])
def test_sharded_search_names_its_cross_chip_merge(indexes, backend,
                                                   stages):
    """On a one-device "data" mesh: the fused-kernel shard body keeps
    the one-chip stage names, and both bodies' merges across chips sit
    under ``shard_merge``."""
    from repro.distributed.sharding import make_mesh_auto

    idx = dataclasses.replace(indexes["two-step"], backend=backend)
    view = idx.shard(make_mesh_auto((1,), ("data",)))
    assert view.backend == backend
    q = np.zeros((8, 16), np.float32)
    text = jax.jit(lambda x: view.search(x)).lower(q).as_text(
        dialect="hlo", debug_info=True)
    # the shard_map region's names start at its own scopes
    names = re.findall(r'op_name="([^"]*)"', text)
    found = {s for s in ALL | {"shard_merge"}
             if any(re.search(f"(^|/){s}/", n) for n in names)}
    assert found == stages
