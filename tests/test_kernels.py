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


# ---------------------------------------------- narrowed contractions ----
# Each fused search kernel contracts only over the codebooks its pass
# sums: the fast set in crude, the rest in refine.  The narrowed kernels
# are held to the masked full-width ones (books=None) and to the jnp
# gather sums.  Tables are quarter-integers, so every sum is exact in
# any order and ties between rows are common.

NARROW_FAST = {
    "interleaved": lambda K: (1, 5),
    "one_fast": lambda K: (K - 1,),
    "one_slow": lambda K: tuple(b for b in range(K) if b != 2),
}


def _narrow_problem(key, K, m, n, nq, fast_ids):
    from repro.kernels.stages import crude_lut_operands, slow_lut_operand
    codes = jax.random.randint(key, (n, K), 0, m)
    luts = 8.0 + jnp.round(4.0 * jax.random.normal(
        jax.random.fold_in(key, 1), (nq, K, m))) / 4.0
    fast = np.zeros(K, bool)
    fast[list(fast_ids)] = True
    return codes, luts, fast, crude_lut_operands, slow_lut_operand


def _masked_operands(luts, fast, quantized, code_bits):
    """Today's full-width operands: every codebook, the mask multiplied
    in (the path a traced mask keeps)."""
    from repro.index.base import (fastscan_kernel_operands, pad_luts_even,
                                  quantized_kernel_operands)
    nq = luts.shape[0]
    if quantized:
        return (fastscan_kernel_operands(luts, jnp.asarray(fast))
                if code_bits == 4
                else quantized_kernel_operands(luts, jnp.asarray(fast)))
    lut = luts * jnp.asarray(fast, luts.dtype)[None, :, None]
    lut = pad_luts_even(lut) if code_bits == 4 else lut
    return lut.reshape(nq, -1), None, None


def _masked_slow(luts, fast, code_bits):
    return _masked_operands(luts, ~fast, False, code_bits)[0]


def _stored(codes, K, code_bits):
    return ops.pack_nibbles(codes, K) if code_bits == 4 else \
        codes.astype(jnp.uint8)


@pytest.mark.parametrize("code_bits,K,m", [(8, 8, 32), (4, 7, 16)])
@pytest.mark.parametrize("fast_set", sorted(NARROW_FAST))
@pytest.mark.parametrize("quantized", [False, True])
def test_narrowed_crude_topk_matches_masked(key, code_bits, K, m, fast_set,
                                            quantized):
    from repro.index.base import lut_sum, quantize_lut
    n, nq, topk = 300, 5, 8
    codes, luts, fast, crude_ops, _ = _narrow_problem(
        key, K, m, n, nq, NARROW_FAST[fast_set](K))
    flat, scale, offset, books = crude_ops(luts, jnp.asarray(fast),
                                           quantized=quantized,
                                           code_bits=code_bits)
    assert books == tuple(np.flatnonzero(fast))
    assert flat.shape == (nq, len(books) * m)
    stored = _stored(codes, K, code_bits)
    opts = dict(block_q=2, block_n=128, interpret=True, code_bits=code_bits)
    crude, vals, idx = ops.batched_crude_topk(
        stored, flat, topk, lut_scale=scale, lut_offset=offset,
        books=books, **opts)
    crude_w, vals_w, idx_w = ops.batched_crude_topk(
        stored, *_masked_operands(luts, fast, quantized, code_bits)[:1],
        topk, lut_scale=scale, lut_offset=offset, **opts)
    table = quantize_lut(luts, jnp.asarray(fast)) if quantized else luts
    want = lut_sum(table, codes, jnp.asarray(fast))
    neg, idx0 = jax.lax.top_k(-want, topk)
    for c, v, i in ((crude, vals, idx), (crude_w, vals_w, idx_w)):
        np.testing.assert_array_equal(np.asarray(i), np.asarray(idx0))
        np.testing.assert_allclose(np.asarray(c), np.asarray(want),
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(v), np.asarray(-neg),
                                   rtol=1e-5)


@pytest.mark.parametrize("code_bits,K,m", [(8, 8, 32), (4, 7, 16)])
@pytest.mark.parametrize("fast_set", sorted(NARROW_FAST))
def test_narrowed_refine_topk_matches_masked(key, code_bits, K, m,
                                             fast_set):
    """The slow pass over the slow codebooks only; a harsh threshold
    leaves some rows fewer survivors than topk (the +inf tail)."""
    from repro.index.base import lut_sum
    n, nq, topk = 300, 5, 8
    codes, luts, fast, _, slow_op = _narrow_problem(
        key, K, m, n, nq, NARROW_FAST[fast_set](K))
    crude0 = lut_sum(luts, codes, jnp.asarray(fast))
    thr = jnp.quantile(crude0, 0.02, axis=1)
    lut_slow, books = slow_op(luts, jnp.asarray(fast), code_bits=code_bits)
    assert books == tuple(np.flatnonzero(~fast))
    assert lut_slow.shape == (nq, len(books) * m)
    stored = _stored(codes, K, code_bits)
    opts = dict(block_q=2, block_n=128, interpret=True, code_bits=code_bits)
    dist, idx = ops.batched_refine_topk(stored, lut_slow, crude0, thr, topk,
                                        books=books, **opts)
    dist_w, idx_w = ops.batched_refine_topk(
        stored, _masked_slow(luts, fast, code_bits), crude0, thr, topk,
        **opts)
    full0 = crude0 + lut_sum(luts, codes, jnp.asarray(~fast))
    neg, idx0 = jax.lax.top_k(
        -jnp.where(crude0 < thr[:, None], full0, jnp.inf), topk)
    assert np.isinf(np.asarray(neg)).any()
    for d, i in ((dist, idx), (dist_w, idx_w)):
        np.testing.assert_array_equal(np.asarray(i), np.asarray(idx0))
        np.testing.assert_allclose(np.asarray(d), np.asarray(-neg),
                                   rtol=1e-5)


@pytest.mark.parametrize("code_bits,K,m", [(8, 8, 32), (4, 7, 16)])
@pytest.mark.parametrize("fast_set", sorted(NARROW_FAST))
def test_narrowed_ivf_kernels_match_masked(key, code_bits, K, m, fast_set):
    """The IVF slab kernels, crude then refine, over a slab with invalid
    (-1) candidates and a width off the tile grid."""
    from repro.index.base import lut_sum
    nc, nq, topk = 300, 5, 8
    codes, luts, fast, crude_ops, slow_op = _narrow_problem(
        key, K, m, nq * nc, nq, NARROW_FAST[fast_set](K))
    slab = codes.reshape(nq, nc, K)
    ids = jnp.where(jax.random.bernoulli(jax.random.fold_in(key, 2), 0.2,
                                         (nq, nc)), -1,
                    jnp.arange(nq * nc).reshape(nq, nc))
    stored = _stored(slab, K, code_bits)
    opts = dict(block_q=2, block_n=128, interpret=True, code_bits=code_bits)
    flat, _, _, cbooks = crude_ops(luts, jnp.asarray(fast), quantized=False,
                                   code_bits=code_bits)
    lut_slow, sbooks = slow_op(luts, jnp.asarray(fast), code_bits=code_bits)
    assert flat.shape == (nq, len(cbooks) * m)
    assert lut_slow.shape == (nq, len(sbooks) * m)
    crude0 = jnp.where(ids >= 0, lut_sum(luts, slab, jnp.asarray(fast)),
                       jnp.inf)
    thr = jnp.quantile(jnp.where(ids >= 0, crude0, 1e9), 0.03, axis=1)
    full0 = crude0 + lut_sum(luts, slab, jnp.asarray(~fast))
    neg_c, pos_c = jax.lax.top_k(-crude0, topk)
    neg, pos = jax.lax.top_k(
        -jnp.where(crude0 < thr[:, None], full0, jnp.inf), topk)
    assert np.isinf(np.asarray(neg)).any()
    for crude_lut, slow_lut, cb, sb in (
            (flat, lut_slow, cbooks, sbooks),
            (_masked_operands(luts, fast, False, code_bits)[0],
             _masked_slow(luts, fast, code_bits), None, None)):
        crude, vals, cpos = ops.ivf_crude_topk(stored, ids, crude_lut, topk,
                                               books=cb, **opts)
        np.testing.assert_array_equal(np.asarray(cpos), np.asarray(pos_c))
        np.testing.assert_allclose(np.asarray(crude), np.asarray(crude0),
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(vals), np.asarray(-neg_c),
                                   rtol=1e-5)
        dist, rpos = ops.ivf_refine_topk(stored, slow_lut, crude, thr, topk,
                                         books=sb, **opts)
        np.testing.assert_array_equal(np.asarray(rpos), np.asarray(pos))
        np.testing.assert_allclose(np.asarray(dist), np.asarray(-neg),
                                   rtol=1e-5)


@pytest.mark.parametrize("books", [None, (0, 1, 2, 3), (3, 1), (2,)])
def test_flat_onehot_has_one_block_per_listed_codebook(key, books):
    from repro.kernels.adc import flat_onehot
    n, K, m = 37, 4, 16
    codes = jax.random.randint(key, (n, K), 0, m)
    got = flat_onehot(codes, K, m, jnp.float32, books)
    listed = range(K) if books is None else books
    want = np.concatenate([np.eye(m)[np.asarray(codes[:, b])]
                           for b in listed], axis=1)
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("fast,quantized,code_bits,K,books", [
    (None, False, 8, 8, (8, 0)),        # one-step ADC: no refine pass
    ((1, 5), False, 8, 8, (2, 6)),
    ((1, 5), True, 8, 8, (2, 6)),
    (None, False, 4, 7, (8, 0)),        # odd K: the zero sentinel book
    ((6,), True, 4, 7, (1, 6)),         # narrowed: the sentinel never
])
def test_kernel_columns_are_the_operand_widths(fast, quantized, code_bits,
                                               K, books):
    from repro.kernels.stages import kernel_columns
    m = 16
    mask = None if fast is None else np.isin(np.arange(K), fast)
    assert kernel_columns(mask, K, m, quantized=quantized,
                          code_bits=code_bits) == (books[0] * m,
                                                   books[1] * m)
