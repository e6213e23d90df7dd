"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("n,K,m", [(64, 2, 16), (512, 8, 64), (1000, 16, 256),
                                   (4096, 4, 256)])
def test_adc_sweep(key, n, K, m):
    codes = jax.random.randint(key, (n, K), 0, m)
    lut = jax.random.normal(jax.random.fold_in(key, 1), (K, m))
    got = ops.adc(codes, lut, interpret=True)
    want = ref.adc_ref(codes, lut)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


@pytest.mark.parametrize("n,K,m,kf", [(256, 8, 32, 2), (999, 16, 64, 4)])
def test_two_step_sweep(key, n, K, m, kf):
    codes = jax.random.randint(key, (n, K), 0, m)
    lut = jax.random.normal(jax.random.fold_in(key, 1), (K, m))
    fast = jnp.zeros((K,), bool).at[:kf].set(True)
    thr = 0.3
    crude, passed = ops.two_step(codes, lut, fast, thr, interpret=True)
    c0, p0 = ref.two_step_ref(codes, lut, fast, thr)
    np.testing.assert_allclose(np.asarray(crude), np.asarray(c0), atol=1e-4)
    np.testing.assert_array_equal(np.asarray(passed), np.asarray(p0))


@pytest.mark.parametrize("n,nq,K,m,topk", [
    (300, 5, 4, 16, 8),        # non-divisible n and nq
    (1024, 8, 8, 32, 10),      # divisible
    (999, 3, 2, 64, 7),        # tiny K, odd n
])
@pytest.mark.parametrize("ties", [False, True])
def test_batched_crude_topk_sweep(key, n, nq, K, m, topk, ties):
    """``ties``: integer LUT entries, so many points share a distance
    across point tiles and the merge must keep ``top_k``'s lowest-index
    tie-break."""
    codes = jax.random.randint(key, (n, K), 0, m)
    luts = jax.random.normal(jax.random.fold_in(key, 1), (nq, K, m))
    if ties:
        luts = jnp.round(luts)
    crude, vals, idx = ops.batched_crude_topk(
        codes, luts.reshape(nq, K * m), topk, block_q=2, block_n=128,
        interpret=True)
    crude0 = ref.batched_crude_ref(codes, luts)
    np.testing.assert_allclose(np.asarray(crude), np.asarray(crude0),
                               atol=1e-4)
    neg, idx0 = jax.lax.top_k(-crude0, topk)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx0))
    np.testing.assert_allclose(np.asarray(vals), np.asarray(-neg), atol=1e-4)


@pytest.mark.parametrize("n,nq,K,m,topk,q_thr", [
    (300, 5, 4, 16, 8, 0.3),
    (999, 4, 8, 32, 10, 0.005),  # harsh threshold: fewer passers than topk
])
def test_batched_refine_topk_sweep(key, n, nq, K, m, topk, q_thr):
    """Fused eq. 2 test + slow sum + in-kernel top-k merge vs the
    monolithic oracle — exact index parity incl. the +inf pruned tail."""
    codes = jax.random.randint(key, (n, K), 0, m)
    luts = jax.random.normal(jax.random.fold_in(key, 1), (nq, K, m))
    crude0 = ref.batched_crude_ref(codes, luts)
    slow_luts = luts * 0.5
    thr = jnp.quantile(crude0, q_thr, axis=1)
    dist, idx = ops.batched_refine_topk(
        codes, slow_luts.reshape(nq, K * m), crude0, thr, topk,
        block_q=2, block_n=128, interpret=True)
    full0 = crude0 + ref.batched_crude_ref(codes, slow_luts)
    ranked0 = jnp.where(crude0 < thr[:, None], full0, jnp.inf)
    neg, idx0 = jax.lax.top_k(-ranked0, topk)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx0))
    np.testing.assert_allclose(np.asarray(dist), np.asarray(-neg), atol=1e-4)


@pytest.mark.parametrize("n,d,m", [(128, 8, 4), (3000, 48, 96),
                                   (1024, 128, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kmeans_sweep(key, n, d, m, dtype):
    x = jax.random.normal(key, (n, d), dtype)
    cent = jax.random.normal(jax.random.fold_in(key, 1), (m, d), dtype)
    ids, dist = ops.kmeans_assign(x, cent, interpret=True)
    ids0, dist0 = ref.kmeans_assign_ref(x, cent)
    # ties under low precision may flip ids; distances must agree
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(dist), np.asarray(dist0),
                               rtol=tol, atol=tol)
    agree = np.mean(np.asarray(ids) == np.asarray(ids0))
    assert agree > (0.999 if dtype == jnp.float32 else 0.98)


@pytest.mark.parametrize("b,sq,sk,h,kvh,dh,causal", [
    (1, 64, 64, 4, 4, 32, True),
    (2, 128, 128, 8, 2, 64, True),
    (1, 64, 256, 4, 1, 32, False),     # cross-length, MQA
    (2, 256, 256, 8, 8, 128, True),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(key, b, sq, sk, h, kvh, dh, causal, dtype):
    q = jax.random.normal(key, (b, sq, h, dh), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, sk, kvh, dh), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, sk, kvh, dh), dtype)
    got = ops.flash_attention(q, k, v, causal=causal, blk_q=64, blk_k=64,
                              interpret=True)
    g = h // kvh
    kk = jnp.repeat(k, g, axis=2)
    vv = jnp.repeat(v, g, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, dh)
    kf = kk.transpose(0, 2, 1, 3).reshape(b * h, sk, dh)
    vf = vv.transpose(0, 2, 1, 3).reshape(b * h, sk, dh)
    want = ref.flash_attention_ref(qf, kf, vf, causal=causal)
    want = want.reshape(b, h, sq, dh).transpose(0, 2, 1, 3)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol)


def test_flash_vs_model_chunked_attention(key):
    """The Pallas kernel and the GSPMD chunked path are interchangeable."""
    from repro.models.attention import chunked_attention
    q = jax.random.normal(key, (2, 256, 8, 64))
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, 256, 2, 64))
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, 256, 2, 64))
    a = ops.flash_attention(q, k, v, causal=True, interpret=True)
    b = chunked_attention(q, k, v, causal=True, chunk=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-4, atol=2e-4)
