"""Trainer layer (DESIGN.md §9): tiled ICM encoding engine invariants
(objective monotone, jnp==pallas==oracle code parity, warm start,
chunk invariance), the padded-chunk database encoder, the scan-compiled
epoch driver (key threading, host-loop equivalence), the Quantizer
protocol, data-parallel training (subprocess under forced host devices
— the in-process suite must keep seeing 1 device, see conftest), and
the uint16 packed-codes regression."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ICQConfig
from repro.core import codebooks as cb
from repro.core import encode as enc
from repro.core.icq import ICQStructure
from repro.index import adc_search, two_step_search
from repro.kernels.ref import icm_encode_gram
from repro.trainer import (Quantizer, encode_database, epoch_batches, fit,
                           make_quantizer)


@pytest.fixture(scope="module")
def icm_problem(key):
    # non-divisible n (prime-ish) to exercise pad/slice paths everywhere
    x = jax.random.normal(key, (517, 16)) * jnp.linspace(0.2, 3.0, 16)
    C = cb.init_residual(key, x, 4, 16, iters=5)
    return x, C


# ------------------------------------------------------- encoding engine ----

def test_icm_objective_non_increasing_per_sweep(icm_problem):
    x, C = icm_problem
    codes0 = enc.encode_pq(x, C)
    errs = [float(cb.quantization_mse(x, C, codes0))]
    for iters in (1, 2, 3):
        codes = enc.icm_encode(x, C, iters, backend="jnp")
        errs.append(float(cb.quantization_mse(x, C, codes)))
    for a, b in zip(errs, errs[1:]):
        assert b <= a + 1e-5


def test_icm_parity_jnp_pallas_oracle_non_divisible(icm_problem):
    x, C = icm_problem
    oracle = icm_encode_gram(x, C, 3)
    jnp_codes = enc.icm_encode(x, C, 3, backend="jnp")
    pl_codes = enc.icm_encode(x, C, 3, backend="pallas", block_n=128,
                              interpret=True)
    np.testing.assert_array_equal(np.asarray(jnp_codes), np.asarray(oracle))
    np.testing.assert_array_equal(np.asarray(pl_codes), np.asarray(jnp_codes))


def test_icm_warm_start_equivalence(icm_problem):
    """Default warm start IS the PQ assignment: passing it explicitly
    must be a no-op, and a one-sweep hand-rolled warm start must match
    a later sweep of the default path."""
    x, C = icm_problem
    default = enc.icm_encode(x, C, 3, backend="jnp")
    explicit = enc.icm_encode(x, C, 3, init_codes=enc.encode_pq(x, C),
                              backend="jnp")
    np.testing.assert_array_equal(np.asarray(default), np.asarray(explicit))
    one = enc.icm_encode(x, C, 1, backend="jnp")
    resumed = enc.icm_encode(x, C, 2, init_codes=one, backend="jnp")
    np.testing.assert_array_equal(np.asarray(resumed), np.asarray(default))


def test_icm_point_chunk_invariance(icm_problem):
    """Encoding is per-point: chunked blocks (ragged tail included)
    assign identical codes."""
    x, C = icm_problem
    full = enc.icm_encode(x, C, 3, backend="jnp")
    chunked = enc.icm_encode(x, C, 3, backend="jnp", point_chunk=128)
    np.testing.assert_array_equal(np.asarray(chunked), np.asarray(full))


def test_icm_pq_codebooks_reduce_to_pq(key):
    """Orthogonal supports: interactions vanish, ICM == the independent
    PQ assignment (why Index.add can use one encode path)."""
    x = jax.random.normal(key, (200, 16))
    C = cb.init_pq(key, x, 4, 8)
    np.testing.assert_array_equal(
        np.asarray(enc.icm_encode(x, C, 3, backend="jnp")),
        np.asarray(enc.encode_pq(x, C)))


def test_encode_database_pads_ragged_chunk_single_compile(icm_problem):
    x, C = icm_problem
    direct = encode_database(x, C, mode="icm", icm_iters=2, chunk=517)
    ragged = encode_database(x, C, mode="icm", icm_iters=2, chunk=200)
    assert ragged.dtype == jnp.uint8
    np.testing.assert_array_equal(np.asarray(ragged), np.asarray(direct))


# ------------------------------------------------------------ epoch driver ----

@pytest.fixture(scope="module")
def train_data():
    from repro.data import make_table1_dataset
    xtr, ytr, _, _ = make_table1_dataset("dataset3")
    return np.asarray(xtr[:900]), np.asarray(ytr[:900])


def test_fit_threads_callers_key(train_data):
    """The seed fit hardcoded PRNGKey(0x5EED) for shuffling; runs must
    now be seeded by the caller's key."""
    xtr, ytr = train_data
    cfg = ICQConfig(d=16, num_codebooks=4, codebook_size=16, num_fast=2)
    kw = dict(mode="icq", epochs=2, batch_size=128)
    m1 = fit(jax.random.PRNGKey(1), xtr, ytr, cfg, **kw)
    m1b = fit(jax.random.PRNGKey(1), xtr, ytr, cfg, **kw)
    m2 = fit(jax.random.PRNGKey(2), xtr, ytr, cfg, **kw)
    np.testing.assert_array_equal(np.asarray(m1.codes), np.asarray(m1b.codes))
    assert not bool(jnp.all(m1.codes == m2.codes))


def test_fit_produces_usable_model(train_data):
    from repro.core import mean_average_precision
    xtr, ytr = train_data
    cfg = ICQConfig(d=16, num_codebooks=4, codebook_size=16, num_fast=2)
    model = fit(jax.random.PRNGKey(0), xtr, ytr, cfg, mode="icq", epochs=4,
                batch_size=128)
    assert model.codes.shape == (900, 4) and model.codes.dtype == jnp.uint8
    r = adc_search(model.embed(xtr[:64]), model.codes, model.C, 10)
    mapv = float(mean_average_precision(r.indices, jnp.asarray(ytr),
                                        jnp.asarray(ytr[:64])))
    assert mapv > 0.5


@pytest.mark.parametrize("rms, unit", [(22.1, 1.0), (16.0, 1.0),
                                       (31.99, 1.0), (32.0, 0.5),
                                       (0.113, 256.0), (500.0, 1 / 16),
                                       (0.0, 1.0)])
def test_fit_unit_brings_the_rms_entry_into_16_to_32(rms, unit):
    from repro.trainer.joint import fit_unit
    assert fit_unit(np.full((8, 4), rms, np.float32)) == unit


@pytest.mark.parametrize("scale", [1 / 256, 1.0, 256.0])
def test_fit_is_the_same_for_rows_scaled_by_a_power_of_two(scale):
    """The fit's absolute constants (lr, tau, the prior's floors) act in
    the fit's unit, not the rows': rows times a power of two fit to the
    same codes and fast set, with codebooks, Lambda and sigma scaled
    exactly."""
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (640, 16))
                   * jnp.linspace(0.2, 3.0, 16))
    y = np.zeros((640,), np.int32)
    cfg = ICQConfig(d=16, num_codebooks=4, codebook_size=16, num_fast=2)
    kw = dict(embed_kind="identity", mode="icq", epochs=2, batch_size=128)
    ref = fit(jax.random.PRNGKey(1), x, y, cfg, **kw)
    got = fit(jax.random.PRNGKey(1), x * np.float32(scale), y, cfg, **kw)
    np.testing.assert_array_equal(np.asarray(got.codes), np.asarray(ref.codes))
    np.testing.assert_array_equal(np.asarray(got.structure.fast_mask),
                                  np.asarray(ref.structure.fast_mask))
    s = np.float32(scale)
    np.testing.assert_array_equal(np.asarray(got.C), np.asarray(ref.C) * s)
    np.testing.assert_array_equal(np.asarray(got.lam),
                                  np.asarray(ref.lam) * s * s)
    assert float(got.structure.sigma) == float(ref.structure.sigma) * scale ** 2


@pytest.mark.parametrize("num_fast", [1, 2, 3])
def test_icq_warm_start_gives_each_codebook_its_own_dimensions(num_fast):
    """Fast codebooks start on psi, slow ones on the rest, no dimension
    shared, and the fit exports that same psi and fast set: the export's
    projection cuts nothing the warm start fit."""
    from repro.trainer.joint import init_train_state
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(4), (1024, 16))
                   * jnp.linspace(0.2, 3.0, 16))
    y = np.zeros((1024,), np.int32)
    cfg = ICQConfig(d=16, num_codebooks=4, codebook_size=16,
                    num_fast=num_fast)
    st = init_train_state(jax.random.PRNGKey(0), cfg, embed_kind="identity",
                          d_raw=16, mode="icq", sample_batch=(x, y))
    support = np.any(np.asarray(st["params"]["C"]) != 0, axis=1)    # (K, d)
    assert support.any(axis=1).all()
    assert (support.sum(axis=0) == 1).all()
    psi = support[:num_fast].any(axis=0)
    model = fit(jax.random.PRNGKey(0), x, y, cfg, embed_kind="identity",
                mode="icq", epochs=1, batch_size=256)
    np.testing.assert_array_equal(np.asarray(model.structure.xi), psi)
    np.testing.assert_array_equal(np.asarray(model.structure.fast_mask),
                                  np.arange(4) < num_fast)


def test_epoch_batches_permutes_and_drops_tail(train_data):
    xtr, ytr = train_data
    xb, yb = epoch_batches(jax.random.PRNGKey(3), xtr, ytr, 128)
    assert xb.shape == (7, 128, 64) and yb.shape == (7, 128)
    # a permutation, not a slice: rows are a subset of the originals
    flat = np.asarray(xb).reshape(-1, 64)
    assert not np.array_equal(flat, np.asarray(xtr[: 7 * 128]))


_DP_SCRIPT = """
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import ICQConfig
from repro.distributed.sharding import make_mesh_auto
from repro.trainer import fit
from repro.data import make_table1_dataset

xtr, ytr, _, _ = make_table1_dataset("dataset3")
xtr, ytr = np.asarray(xtr[:512]), np.asarray(ytr[:512])
cfg = ICQConfig(d=16, num_codebooks=4, codebook_size=16, num_fast=2)
mesh = make_mesh_auto((4,), ("data",))
kw = dict(mode="icq", epochs=2, batch_size=128)
m_dp = fit(jax.random.PRNGKey(1), xtr, ytr, cfg, mesh=mesh, **kw)
m_sd = fit(jax.random.PRNGKey(1), xtr, ytr, cfg, **kw)
agree = float(jnp.mean((m_dp.codes == m_sd.codes).astype(jnp.float32)))
assert agree > 0.98, agree           # identical up to float reassociation
assert jnp.allclose(m_dp.lam, m_sd.lam, rtol=1e-3, atol=1e-5)
print("DP_OK", agree)
"""


def test_data_parallel_fit_matches_single_device():
    """shard_map epoch driver under 4 forced host devices: pmean'd
    grads + global batch moments track the single-device run (exact up
    to float reassociation accumulating through SGD)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _DP_SCRIPT],
                          capture_output=True, text=True, env=env,
                          timeout=600,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr
    assert "DP_OK" in proc.stdout


# ------------------------------------------------------ quantizer protocol ----

def test_make_quantizer_registry(key, train_data):
    xtr, ytr = train_data
    cfg = ICQConfig(d=16, num_codebooks=4, codebook_size=16, num_fast=2)
    for kind in ("icq", "pq", "opq", "cq"):
        q = make_quantizer(kind, cfg)
        assert isinstance(q, Quantizer)
    with pytest.raises(ValueError, match="unknown quantizer"):
        make_quantizer("nope", cfg)
    # protocol round-trip on the cheapest unsupervised kind
    q = make_quantizer("pq", cfg)
    x16 = np.asarray(xtr[:300, :16])
    state = q.init(key, x16)
    state = q.step(state, x16)
    model = q.finalize(state, x16)
    assert model.codes.shape == (300, 4)
    np.testing.assert_array_equal(
        np.asarray(enc.unpack_codes(model.codes)),
        np.asarray(enc.encode_pq(jnp.asarray(x16), model.C)))


def test_joint_quantizer_steps_reduce_loss(key, train_data):
    xtr, ytr = train_data
    cfg = ICQConfig(d=16, num_codebooks=4, codebook_size=16, num_fast=2)
    q = make_quantizer("icq", cfg)
    state = q.init(key, xtr, ytr)
    losses = []
    for i in range(12):
        state = q.step(state, (xtr[:256], ytr[:256]))
        losses.append(float(state["last_metrics"]["total"]))
    assert losses[-1] < losses[0]


# ------------------------------------------------- uint16 packed codes ----

def test_uint16_codes_supported_end_to_end(key):
    """Regression (m > 256): pack_codes emits uint16 and every engine
    accepts it — codes widen to int32 at the LUT-sum / kernel boundary,
    so rankings are identical to unpacked int32 codes."""
    n, K, m, d = 400, 2, 512, 8
    codes_i32 = jax.random.randint(key, (n, K), 0, m)
    packed = enc.pack_codes(codes_i32, m)
    assert packed.dtype == jnp.uint16
    np.testing.assert_array_equal(np.asarray(enc.unpack_codes(packed)),
                                  np.asarray(codes_i32))
    C = jax.random.normal(jax.random.fold_in(key, 1), (K, m, d)) * 0.3
    st = ICQStructure(xi=jnp.ones((d,), bool),
                      fast_mask=jnp.asarray([True, False]),
                      sigma=jnp.asarray(1.0))
    q = jax.random.normal(jax.random.fold_in(key, 2), (5, d))
    for backend, kw in (("jnp", {}), ("pallas", dict(interpret=True))):
        r_packed = adc_search(q, packed, C, 7, backend=backend, **kw)
        r_i32 = adc_search(q, codes_i32, C, 7, backend=backend, **kw)
        np.testing.assert_array_equal(np.asarray(r_packed.indices),
                                      np.asarray(r_i32.indices))
        r2_packed = two_step_search(q, packed, C, st, 7, backend=backend,
                                    **kw)
        r2_i32 = two_step_search(q, codes_i32, C, st, 7, backend=backend,
                                 **kw)
        np.testing.assert_array_equal(np.asarray(r2_packed.indices),
                                      np.asarray(r2_i32.indices))
