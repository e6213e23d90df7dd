"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e
at serving widths: n = 2^20 codes, K = 8 codebooks of m = 256, d = 128,
nq = 64 queries, plus the 4-bit fast-scan geometry (K = 16, m = 16).
The ICM encode kernel compiles at one encode chunk (8192 rows, the
``encode.chunk`` default), the shape the engine calls it with.

The TPU compiler is installed with jax, so these compiles need no chip:
they catch what interpret mode cannot — primitives Mosaic does not
lower, block shapes off the (8, 128) tiling, kernels over the scoped
VMEM limit.  A compile that passes is not a chip run.

The topology is described inside a module-scoped fixture, never at
import time: only one process may load the TPU library, and every test
worker imports this file."""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import batched_search as bs
from repro.kernels.icm_encode import icm_encode_pallas

N, K, M, D, NQ, TOPK = 2 ** 20, 8, 256, 128, 64, 10
FS_K, FS_M = 16, 16                     # fast-scan: 16 nibble codebooks
ENCODE_ROWS = 8192
SLAB = 16 * 2048                        # IVF candidates: 16 probed lists
# an interleaved fast set of 2 codebooks: the narrowed crude kernels
# contract over 2 * M = 512 columns, the refine kernels over 6 * M = 1536
FAST = (1, 5)
SLOW = tuple(b for b in range(K) if b not in FAST)


@pytest.fixture(scope="module")
def v5e_2x2():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev_log = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev_cache)
    cc.reset_cache()
    if prev_log is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = prev_log


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2.devices[0])


def _kernel_call(name):
    """(fn, [(shape, dtype), ...]) for one main-path kernel."""
    f32, i8, u8, i32 = jnp.float32, jnp.int8, jnp.uint8, jnp.int32
    codes, lut = ((N, K), u8), ((NQ, K * M), f32)
    col = ((NQ,), f32)
    if name == "crude_topk_f32":
        return (lambda c, l: bs.crude_topk_pallas(
            c, l, topk=TOPK, interpret=False), [codes, lut])
    if name == "crude_topk_int8":
        return (lambda c, l, s, o: bs.crude_topk_pallas(
            c, l, s, o, topk=TOPK, interpret=False),
            [codes, ((NQ, K * M), i8), col, col])
    if name == "refine_topk":
        return (lambda c, l, cr, t: bs.refine_topk_pallas(
            c, l, cr, t, topk=TOPK, interpret=False),
            [codes, lut, ((NQ, N), f32), col])
    if name == "ivf_crude_topk":
        return (lambda c, i, l: bs.ivf_crude_topk_pallas(
            c, i, l, topk=TOPK, interpret=False),
            [((NQ, SLAB, K), u8), ((NQ, SLAB), i32), lut])
    if name == "ivf_refine_topk":
        return (lambda c, l, cr, t: bs.ivf_refine_topk_pallas(
            c, l, cr, t, topk=TOPK, interpret=False),
            [((NQ, SLAB, K), u8), lut, ((NQ, SLAB), f32), col])
    if name == "crude_topk_narrowed":
        return (lambda c, l: bs.crude_topk_pallas(
            c, l, topk=TOPK, interpret=False, books=FAST),
            [codes, ((NQ, len(FAST) * M), f32)])
    if name == "refine_topk_narrowed":
        return (lambda c, l, cr, t: bs.refine_topk_pallas(
            c, l, cr, t, topk=TOPK, interpret=False, books=SLOW),
            [codes, ((NQ, len(SLOW) * M), f32), ((NQ, N), f32), col])
    if name == "ivf_crude_topk_narrowed":
        return (lambda c, i, l: bs.ivf_crude_topk_pallas(
            c, i, l, topk=TOPK, interpret=False, books=FAST),
            [((NQ, SLAB, K), u8), ((NQ, SLAB), i32),
             ((NQ, len(FAST) * M), f32)])
    if name == "ivf_refine_topk_narrowed":
        return (lambda c, l, cr, t: bs.ivf_refine_topk_pallas(
            c, l, cr, t, topk=TOPK, interpret=False, books=SLOW),
            [((NQ, SLAB, K), u8), ((NQ, len(SLOW) * M), f32),
             ((NQ, SLAB), f32), col])
    if name == "fastscan_crude_topk":
        return (lambda c, l, s, o: bs.fastscan_crude_topk_pallas(
            c, l, s, o, topk=TOPK, interpret=False),
            [((N, FS_K // 2), u8), ((NQ, FS_K * FS_M), i8), col, col])
    if name == "icm_encode":
        return (lambda x, c0, C: icm_encode_pallas(x, c0, C,
                                                   interpret=False),
                [((ENCODE_ROWS, D), f32), ((ENCODE_ROWS, K), i32),
                 ((K, M, D), f32)])
    raise KeyError(name)


@pytest.mark.parametrize("name", [
    "crude_topk_f32", "crude_topk_int8", "refine_topk", "ivf_crude_topk",
    "ivf_refine_topk", "fastscan_crude_topk", "icm_encode",
    "crude_topk_narrowed", "refine_topk_narrowed", "ivf_crude_topk_narrowed",
    "ivf_refine_topk_narrowed"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _kernel_call(name)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# the names the benchmark's kernel metrics match in the device trace
KERNEL_OP = re.compile(r"%((ivf_)?(crude|refine)_topk_pallas(\.\d+)?) = "
                       r".*op_name=\"([^\"]*)\"")


@pytest.mark.parametrize("kind,traced", [
    pytest.param("two-step", False, id="two-step"),
    pytest.param("ivf", False, id="ivf"),
    pytest.param("two-step", True, id="two-step-traced-structure"),
    pytest.param("ivf", True, id="ivf-traced-structure")])
def test_search_kernels_keep_their_names_under_stage_scopes(one_chip, kind,
                                                            traced):
    """The whole search compiled for the chip: the stage scopes reach
    the kernels' ``op_name`` and leave their instruction names, which
    the trace reports, as they were.  With the structure a constant, as
    in the served programs, each kernel is narrowed to its pass's
    codebooks; with it an argument (a traced fast mask) both kernels
    take the masked full-width tables."""
    import dataclasses

    import numpy as np

    from repro.core.icq import ICQStructure
    from repro.index import make_index

    n, kk, m, d = 4096, 8, 256, 128
    rng = np.random.default_rng(0)
    emb = rng.standard_normal((n, d)).astype(np.float32)
    codes = rng.integers(0, m, (n, kk)).astype(np.uint8)
    C = rng.standard_normal((kk, m, d)).astype(np.float32)
    st = ICQStructure(xi=np.arange(d) < 64,
                      fast_mask=np.isin(np.arange(kk), FAST),
                      sigma=np.float32(1.0))
    opts = dict(emb_db=emb, n_lists=16, n_probe=4) if kind == "ivf" else {}
    idx = make_index(kind, codes, C, st, topk=TOPK, backend="pallas",
                     **opts)
    idx = dataclasses.replace(idx, interpret=False)
    fields = ["codes", "C"] + (["structure"] if traced else []) + (
        ["ivf", "list_codes"] if kind == "ivf" else [])

    def search(q, *vals):
        return dataclasses.replace(idx, **dict(zip(fields, vals))).search(q)

    def shape(a):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype,
                                           sharding=one_chip), a)

    args = [shape(np.zeros((NQ, d), np.float32))] + [
        shape(getattr(idx, f)) for f in fields]
    text = jax.jit(search).lower(*args).compile().as_text()
    found = {m.group(3): m.group(5) for m in KERNEL_OP.finditer(text)}
    assert set(found) == {"crude", "refine"}
    # the shared-codes kernels take each query's table as three bf16
    # parts, stacked on the row axis (``batched_search.split_lut``)
    rows = 3 * NQ if kind == "two-step" else NQ
    if traced:
        assert f"f32[{rows},{kk * m}]" in text
    else:
        # fast codebooks (1, 5) in crude, the other six in refine
        assert (f"f32[{rows},{2 * m}]" in text
                and f"f32[{rows},{6 * m}]" in text)
    for stage, op_name in found.items():
        assert f"/{stage}/" in op_name


# ------------------------------------------- the four-chip Deep-10M cell --
# big-ann-benchmarks deep-10M over a v5e 2x2 host: 10M rows of d = 96,
# 2.5M a chip, 64-bit codes (K = 8 of m = 256), query tiles of 64
DEEP_N, DEEP_D, DEEP_LEARN, DEEP_POOL = 10_000_000, 96, 100_000, 10_000
GB = 1e9


def _bytes(compiled):
    """(temporary, all) bytes of a compiled program on one chip."""
    mem = compiled.memory_analysis()
    temp = mem.temp_size_in_bytes
    return temp, (temp + mem.argument_size_in_bytes
                  + mem.output_size_in_bytes - mem.alias_size_in_bytes)


def test_sharded_pallas_search_compiles_for_four_chips(v5e_2x2):
    """The row-sharded two-step search on the fused kernels, one program
    over the host's four chips at the cell's per-chip shapes.  Its
    temporaries per chip stay under 2 GB: the dense crude matrix of a
    64-query tile over 2.5M rows is 0.64 GB and the refine kernel's
    +inf-padded copy of it another 0.64 GB; the rest is the kernels'
    block-padded codes (20 MB) and the (64, k) merges."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.index.sharded import two_step_program

    mesh = Mesh(np.asarray(v5e_2x2.devices).reshape(4), ("data",))
    fn = two_step_program(mesh, n=DEEP_N, topk=TOPK, K=K,
                          fast=np.isin(np.arange(K), FAST),
                          backend="pallas", interpret=False)
    rep, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    args = [jax.ShapeDtypeStruct((NQ, DEEP_D), jnp.float32, sharding=rep),
            jax.ShapeDtypeStruct((K, M, DEEP_D), jnp.float32, sharding=rep),
            jax.ShapeDtypeStruct((K,), jnp.bool_, sharding=rep),
            jax.ShapeDtypeStruct((), jnp.float32, sharding=rep),
            jax.ShapeDtypeStruct((4,), jnp.bool_, sharding=rep),
            jax.ShapeDtypeStruct((DEEP_N, K), jnp.uint8, sharding=rows)]
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    found = {m.group(3) for m in KERNEL_OP.finditer(text)}
    assert found == {"crude", "refine"}
    # each kernel narrowed to its pass's codebooks (three bf16 parts a
    # query row), on 2.5M rows a chip
    assert (f"f32[{3 * NQ},{2 * M}]" in text
            and f"f32[{3 * NQ},{6 * M}]" in text)
    assert f"u8[{DEEP_N // 4},{K}]" in text
    assert re.search(r"all-gather(-start)?(\.\d+)? = ", text)
    temp, _ = _bytes(compiled)
    assert temp < 2 * GB, temp


def _setup_program(name):
    """(fn, [(shape, dtype), ...]) of one blocked set-up or check
    program of the benchmark at deep-10M's sizes on chip 0."""
    import json
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from bench import gen, reference, truth

    f32, i32 = jnp.float32, jnp.int32
    if name == "draw":
        with open(os.path.join(root, "bench", "configs",
                               "deep10m-icq64-twostep.json")) as f:
            params = json.load(f)["assumed"]["generator"]
        p = tuple(sorted((k, float(v)) for k, v in params.items()))
        return (lambda key: gen._parts(
            key, sizes=(DEEP_LEARN, DEEP_N, DEEP_POOL), d=DEEP_D, p=p),
            [((2,), jnp.uint32)])
    if name == "exact_neighbours":
        blocks = -(-DEEP_POOL // 128)
        return (lambda q, x, s, fr, bv, bi: truth._merge_rows(
            q, x, s, fr, (bv, bi), k=TOPK, rows=truth.ROW_BLOCK, block=128),
            [((DEEP_POOL, DEEP_D), f32), ((DEEP_N, DEEP_D), f32),
             ((), i32), ((), i32), ((blocks, 128, TOPK), f32),
             ((blocks, 128, TOPK), i32)])
    if name == "icm_codes":
        return (lambda x, C: reference.icm_codes(x, C, iters=3),
                [((DEEP_N, DEEP_D), f32), ((K, M, DEEP_D), f32)])
    if name == "two_step_block":
        return (lambda q, c, C, fast, sigma, ans: reference.two_step_block(
            q, c, C, fast, sigma, ans, topk=TOPK),
            [((NQ, DEEP_D), f32), ((DEEP_N, K), i32),
             ((K, M, DEEP_D), f32), ((K,), jnp.bool_), ((), f32),
             ((NQ, TOPK), i32)])
    raise KeyError(name)


@pytest.mark.parametrize("name", ["draw", "exact_neighbours", "icm_codes",
                                  "two_step_block"])
def test_blocked_setup_fits_one_chip_at_deep10m(one_chip, name):
    """The benchmark's set-up and check programs over the base rows hold
    one block at a time: at 10M rows of d = 96 on one chip each needs at
    most 2 GB of temporaries and 8 GB in all (the base itself is
    3.84 GB)."""
    fn, shapes = _setup_program(name)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    temp, total = _bytes(jax.jit(fn).lower(*args).compile())
    assert temp <= 2 * GB and total <= 8 * GB, (temp, total)
