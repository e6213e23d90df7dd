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


# ------------------------------------------- the exact bf16 LUT split ----
# The shared-codes kernels dot an f32 table as three bfloat16 parts at
# default precision (``batched_search.split_lut``).  On the CPU that
# dot runs at full f32, so a part that is not exactly a bfloat16 would
# pass every kernel test here and lose bits on the TPU: the split is
# held to exactness on its own.

def _split_values():
    """f32 values spanning 1e-30 to 1e30 with both signs, zeros, powers
    of two, full 24-bit mantissas, and ties at half a bf16 ulp of hi
    and of mid."""
    rng = np.random.default_rng(17)
    sign = rng.choice([-1.0, 1.0], 300_000)
    wide = sign[:200_000] * 10.0 ** rng.uniform(-30, 30, 200_000)
    mant = rng.integers(2 ** 23, 2 ** 24, 100_000)
    full = sign[200_000:] * np.ldexp(mant, rng.integers(-40, 40, 100_000))
    # mantissa bits 8-15 and 0-7 each exactly half a bf16 ulp: a tie
    # when hi rounds and again when mid does
    tie_mant = (rng.integers(128, 256, 4096) << 16) | 0x8080
    ties = np.ldexp(tie_mant, rng.integers(-90, 70, 4096) - 23)
    pow2 = np.ldexp(1.0, np.arange(-99, 100))
    return np.concatenate([wide, full, ties, -ties, pow2, -pow2,
                           [0.0, -0.0]]).astype(np.float32)


def test_bf16_split_parts_are_bf16_and_sum_back_exactly():
    from repro.kernels.batched_search import bf16_parts
    x = _split_values()
    assert np.all(np.isfinite(x)) and np.abs(x[x != 0]).min() >= 1e-30
    parts = [np.asarray(p) for p in jax.jit(bf16_parts)(jnp.asarray(x))]
    for p in parts:
        np.testing.assert_array_equal(
            p, np.asarray(jnp.asarray(p).astype(jnp.bfloat16)
                          .astype(jnp.float32)))
    back = (parts[0] + parts[1]) + parts[2]
    np.testing.assert_array_equal(back, x)
    # bit for bit; -0.0 comes back as +0.0, which sums alike
    nz = x != 0
    np.testing.assert_array_equal(back[nz].view(np.uint32),
                                  x[nz].view(np.uint32))
    # the ties took the split through all three parts
    assert np.count_nonzero(parts[2]) > 0


def test_split_lut_stacks_each_query_tiles_parts():
    from repro.kernels.batched_search import bf16_parts, split_lut
    lut = jnp.asarray(_split_values()[:6 * 40].reshape(6, 40))
    got = np.asarray(split_lut(lut, 2))
    hi, mid, lo = (np.asarray(p) for p in bf16_parts(lut))
    for t in range(3):
        rows = slice(2 * t, 2 * t + 2)
        np.testing.assert_array_equal(
            got[6 * t:6 * t + 6], np.concatenate([hi[rows], mid[rows],
                                                  lo[rows]]))


def _full_mantissa_luts(key, nq, K, m):
    """Tables of mixed signs whose entries carry full 24-bit mantissas,
    so dropping any bf16 part of the split shows in the sums."""
    rng = np.random.default_rng(int(jax.random.randint(key, (), 0, 2 ** 30)))
    mant = rng.integers(2 ** 23, 2 ** 24, (nq, K, m))
    sign = rng.choice([-1.0, 1.0], (nq, K, m))
    return (sign * np.ldexp(mant, rng.integers(-25, -21, (nq, K, m)))
            ).astype(np.float32)


def _gather64(luts, codes, books):
    """float64 sums of the listed codebooks' entries, and the sums of
    their magnitudes (the scale of the f32 rounding), each (nq, n)."""
    L, c = np.asarray(luts, np.float64), np.asarray(codes)
    terms = [L[:, b, c[:, b]] for b in books]
    return sum(terms), sum(np.abs(t) for t in terms)


@pytest.mark.parametrize("kernel", ["crude", "refine"])
@pytest.mark.parametrize("narrowed", [False, True])
def test_shared_codes_kernels_sum_full_mantissa_tables(key, kernel,
                                                       narrowed):
    """Distances within 4 f32 ulps of the float64 gather sum, the ulp
    taken at the terms' absolute sum (mixed signs cancel, and an ulp of
    a cancelled sum bounds no f32 summation); ids those of the jnp
    path, on data without ties."""
    from repro.index.base import lut_sum
    n, nq, K, m, topk = 300, 5, 8, 32, 8
    fast = np.isin(np.arange(K), (1, 5))
    codes = jax.random.randint(key, (n, K), 0, m)
    luts = _full_mantissa_luts(jax.random.fold_in(key, 1), nq, K, m)
    opts = dict(block_q=2, block_n=128, interpret=True)
    summed = ~fast if kernel == "refine" else fast
    books = tuple(np.flatnonzero(summed)) if narrowed else tuple(range(K))
    mask = summed if narrowed else np.ones(K, bool)
    flat = luts[:, list(books)].reshape(nq, -1)
    want64, scale = _gather64(luts, codes, books)
    want32 = lut_sum(jnp.asarray(luts), codes, jnp.asarray(mask))
    if kernel == "crude":
        crude, dist, idx = ops.batched_crude_topk(
            codes, flat, topk, books=books if narrowed else None, **opts)
        got = np.asarray(crude)
        _, idx0 = jax.lax.top_k(-want32, topk)
        rows = np.asarray(idx0)
    else:
        crude0 = lut_sum(jnp.asarray(luts), codes, jnp.asarray(fast))
        thr = jnp.quantile(crude0, 0.5, axis=1)
        dist, idx = ops.batched_refine_topk(
            codes, flat, crude0, thr, topk,
            books=books if narrowed else None, **opts)
        _, idx0 = jax.lax.top_k(
            -jnp.where(crude0 < thr[:, None], crude0 + want32, jnp.inf),
            topk)
        rows = np.asarray(idx0)
        pick = np.take_along_axis
        c64 = np.asarray(crude0, np.float64)
        want64 = pick(c64, rows, 1) + pick(want64, rows, 1)
        scale = np.abs(pick(c64, rows, 1)) + pick(scale, rows, 1)
        got = np.asarray(dist)
    np.testing.assert_array_equal(np.asarray(idx), rows)
    if kernel == "crude":
        np.testing.assert_array_equal(
            np.asarray(dist), np.take_along_axis(got, rows, 1))
    ulp = np.spacing(scale.astype(np.float32)).astype(np.float64)
    assert np.max(np.abs(got - want64) / ulp) <= 4.0


# ------------------------------------------------ the lowering witness ----
# Which f32 one-hot dots run at HIGHEST, read off the kernels' jaxprs:
# the shared-codes crude and refine kernels take the split at default
# precision; the IVF slab kernels (lowered to VPU multiply-adds) and the
# ICM encode keep HIGHEST.

def _kernel_dot_precisions(fn, *args):
    """For each pallas_call in fn's jaxpr, the precision of every
    dot_general inside its kernel."""
    kernels = []

    def subjaxprs(v):
        if hasattr(v, "eqns"):
            yield v
        elif hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
            yield v.jaxpr
        elif isinstance(v, (tuple, list)):
            for x in v:
                yield from subjaxprs(x)

    def walk(jaxpr, dots):
        for eqn in jaxpr.eqns:
            inner = dots
            if eqn.primitive.name == "pallas_call":
                inner = []
                kernels.append(inner)
            elif eqn.primitive.name == "dot_general" and dots is not None:
                dots.append(eqn.params["precision"])
            for v in eqn.params.values():
                for j in subjaxprs(v):
                    walk(j, inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr, None)
    return kernels


def _witness_call(name):
    from repro.kernels import batched_search as bs
    from repro.kernels.icm_encode import icm_encode_pallas
    n, nq, K, m, topk, nc = 256, 4, 4, 16, 5, 128
    f32 = jnp.float32
    codes = jnp.zeros((n, K), jnp.uint8)
    slab = jnp.zeros((nq, nc, K), jnp.uint8)
    ids = jnp.zeros((nq, nc), jnp.int32)
    lut, lut2 = jnp.zeros((nq, K * m), f32), jnp.zeros((nq, 2 * m), f32)
    crude, thr = jnp.zeros((nq, n), f32), jnp.zeros((nq,), f32)
    opts = dict(topk=topk, block_q=2, block_n=128)
    return {
        "crude": (lambda: bs.crude_topk_pallas(codes, lut, **opts)),
        "crude_narrowed": (lambda: bs.crude_topk_pallas(
            codes, lut2, books=(1, 3), **opts)),
        "refine": (lambda: bs.refine_topk_pallas(codes, lut, crude, thr,
                                                 **opts)),
        "refine_narrowed": (lambda: bs.refine_topk_pallas(
            codes, lut2, crude, thr, books=(0, 2), **opts)),
        "ivf_crude": (lambda: bs.ivf_crude_topk_pallas(slab, ids, lut,
                                                       **opts)),
        "ivf_refine": (lambda: bs.ivf_refine_topk_pallas(
            slab, lut, crude[:, :nc], thr, **opts)),
        "icm_encode": (lambda: icm_encode_pallas(
            jnp.zeros((n, 8), f32), jnp.zeros((n, K), jnp.int32),
            jnp.zeros((K, m, 8), f32), block_n=128)),
    }[name]


@pytest.mark.parametrize("name,highest", [
    ("crude", False), ("crude_narrowed", False), ("refine", False),
    ("refine_narrowed", False), ("ivf_crude", True), ("ivf_refine", True),
    ("icm_encode", True)])
def test_f32_kernel_dots_run_at_highest_only_off_the_split(name, highest):
    kernels = _kernel_dot_precisions(_witness_call(name))
    assert len(kernels) == 1 and kernels[0]
    at_highest = [p is not None and jax.lax.Precision.HIGHEST in p
                  for p in kernels[0]]
    assert all(at_highest) if highest else not any(at_highest)
