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


@pytest.fixture(scope="module")
def one_chip():
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
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev_cache)
    cc.reset_cache()
    if prev_log is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = prev_log


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
    "ivf_refine_topk", "fastscan_crude_topk", "icm_encode"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _kernel_call(name)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
