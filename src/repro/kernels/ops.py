"""Jit'd public wrappers around the Pallas kernels.

``interpret`` defaults to True off-TPU (this container is CPU-only; TPU
v5e is the compile target) and False on real TPU backends.  The GQA
head-folding for flash attention lives here so the kernel stays MHA.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.adc import adc_pallas
from repro.kernels.batched_search import (crude_topk_pallas,
                                          fastscan_crude_topk_pallas,
                                          ivf_crude_topk_pallas,
                                          ivf_fastscan_crude_topk_pallas,
                                          ivf_refine_topk_pallas,
                                          refine_topk_pallas)
from repro.kernels.icm_encode import icm_encode_pallas
from repro.kernels.two_step import two_step_pallas
from repro.kernels.kmeans import kmeans_assign_pallas
from repro.kernels.flash_attention import flash_attention_pallas
# Shared tile helpers (DESIGN.md §13) re-exported at the ops surface so
# kernel callers get one canonical definition of the padding/merge
# contract instead of re-implementing it per wrapper.
from repro.kernels.stages import (check_quantized_args, init_topk,  # noqa: F401
                                  merge_topk, pad_to,
                                  resolve_kernel_code_bits,
                                  unpack_nibble_tile)


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


# ----------------------------------------------------------- fault hook ----
# The resilience layer's injection point (repro.resilience.faults): every
# public op calls the hook with its stage name before dispatching to the
# kernel, so a seeded FaultInjector can deterministically fail "Pallas"
# stages and drive the engine's jnp failover.  None (the default) is
# free; note that under an outer jit the hook fires at trace time only —
# the serving engine runs eager whenever an injector is attached.
_FAULT_HOOK = None


def set_fault_hook(hook):
    """Install ``hook(stage: str)`` (or None to clear).  Returns the
    previous hook so callers can restore it."""
    global _FAULT_HOOK
    prev = _FAULT_HOOK
    _FAULT_HOOK = hook
    return prev


def _check_faults(stage: str) -> None:
    if _FAULT_HOOK is not None:
        _FAULT_HOOK("kernels." + stage)


def adc(codes, lut, *, block_n: int = 512, interpret=None):
    """ADC LUT sum: codes (n,K) int32, lut (K,m) -> dists (n,) f32."""
    _check_faults("adc")
    it = _default_interpret() if interpret is None else interpret
    return adc_pallas(codes, lut, block_n=block_n, interpret=it)


def two_step(codes, lut, fast_mask, threshold, *, block_n: int = 512,
             interpret=None):
    """Fused crude ADC + eq. 2 margin test -> (crude, passed)."""
    _check_faults("two_step")
    it = _default_interpret() if interpret is None else interpret
    return two_step_pallas(codes, lut, fast_mask, threshold,
                           block_n=block_n, interpret=it)


def batched_crude_topk(codes, lut_flat, topk: int, *, block_q: int = 64,
                       block_n: int = 512, interpret=None,
                       want_crude: bool = True, lut_scale=None,
                       lut_offset=None, code_bits: int = 8, books=None):
    """Batched phase 1: crude LUT sums for every (query, point) pair plus
    the in-kernel running top-k of crude distances.

    codes (n, K) int (packed ok), lut_flat (nq, len(books)*m) tables of
    the summed codebooks ``books`` (static ids, default all K) — f32,
    or int8 with ``lut_scale``/``lut_offset`` (nq,) f32 (quantized-LUT
    mode; crude output is dequantized f32) -> (crude (nq, n) | None,
    cand_vals (nq, topk), cand_idx (nq, topk)); ``want_crude=False``
    skips the dense matrix.  ``code_bits=4`` is fast-scan mode:
    nibble-packed codes (n, ceil(K/2)) uint8 (DESIGN.md §12).
    """
    _check_faults("batched_crude_topk")
    it = _default_interpret() if interpret is None else interpret
    return crude_topk_pallas(codes, lut_flat, lut_scale, lut_offset,
                             topk=topk, block_q=block_q,
                             block_n=block_n, interpret=it,
                             want_crude=want_crude, code_bits=code_bits,
                             books=books)


def batched_refine_topk(codes, lut_flat, crude, thresholds, topk: int, *,
                        block_q: int = 64, block_n: int = 512,
                        interpret=None, code_bits: int = 8, books=None):
    """Batched phase 2: fused eq. 2 test + slow-codebook sum + top-k merge.

    codes (n, K) int, lut_flat (nq, len(books)*m) f32 slow tables of
    the codebooks ``books`` (default all K), crude (nq, n), thresholds
    (nq,) -> (dist (nq, topk), idx (nq, topk)).
    """
    _check_faults("batched_refine_topk")
    it = _default_interpret() if interpret is None else interpret
    return refine_topk_pallas(codes, lut_flat, crude, thresholds, topk=topk,
                              block_q=block_q, block_n=block_n, interpret=it,
                              code_bits=code_bits, books=books)


def ivf_crude_topk(cand_codes, cand_ids, lut_flat, topk: int, *,
                   block_q: int = 8, block_n: int = 128, interpret=None,
                   lut_scale=None, lut_offset=None, code_bits: int = 8,
                   books=None):
    """IVF phase 1 over the gathered candidate slab: crude LUT sums +
    in-kernel running top-k of crude distances (slab positions).

    cand_codes (nq, nc, K) int (packed ok), cand_ids (nq, nc) int32
    global ids (-1 pad), lut_flat (nq, len(books)*m) tables of the
    summed codebooks ``books`` (default all K) — f32, or int8 with
    ``lut_scale``/``lut_offset`` (nq,) f32 (quantized-LUT mode; crude
    output is dequantized f32) -> (crude (nq, nc) with invalid +inf,
    vals (nq, topk), pos (nq, topk)).
    """
    _check_faults("ivf_crude_topk")
    it = _default_interpret() if interpret is None else interpret
    return ivf_crude_topk_pallas(cand_codes, cand_ids, lut_flat, lut_scale,
                                 lut_offset, topk=topk,
                                 block_q=block_q, block_n=block_n,
                                 interpret=it, code_bits=code_bits,
                                 books=books)


def ivf_refine_topk(cand_codes, lut_flat, crude, thresholds, topk: int, *,
                    block_q: int = 8, block_n: int = 128, interpret=None,
                    code_bits: int = 8, books=None):
    """IVF phase 2: fused eq. 2 test + slow-codebook sum + top-k merge
    over the candidate slab (``lut_flat`` covers the codebooks
    ``books``, default all K) -> (dist (nq, topk), pos (nq, topk))."""
    _check_faults("ivf_refine_topk")
    it = _default_interpret() if interpret is None else interpret
    return ivf_refine_topk_pallas(cand_codes, lut_flat, crude, thresholds,
                                  topk=topk, block_q=block_q,
                                  block_n=block_n, interpret=it,
                                  code_bits=code_bits, books=books)


def icm_encode(x, init_codes, C, *, iters: int = 3, block_n: int = 1024,
               interpret=None):
    """Point-tiled ICM encode (DESIGN.md §9): x (n, d), init_codes
    (n, K) warm start, C (K, m, d) -> codes (n, K) int32."""
    _check_faults("icm_encode")
    it = _default_interpret() if interpret is None else interpret
    return icm_encode_pallas(x, init_codes, C, iters=iters,
                             block_n=block_n, interpret=it)


def kmeans_assign(x, cent, *, block_n: int = 1024, interpret=None):
    """Nearest-centroid assignment -> (ids, sq-dists)."""
    _check_faults("kmeans_assign")
    it = _default_interpret() if interpret is None else interpret
    return kmeans_assign_pallas(x, cent, block_n=block_n, interpret=it)


def flash_attention(q, k, v, *, causal: bool = True, blk_q: int = 128,
                    blk_k: int = 128, interpret=None):
    """Causal flash attention with GQA support.

    q: (b, sq, H, dh); k/v: (b, sk, KVH, dh) -> (b, sq, H, dh).
    Query heads are grouped with their KV head and folded into the
    kernel's flat batch*heads axis.
    """
    _check_faults("flash_attention")
    it = _default_interpret() if interpret is None else interpret
    b, sq, h, dh = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    # (b, s, kvh, g, dh) -> (b*kvh*g, s, dh); kv repeated across g
    qf = q.reshape(b, sq, kvh, g, dh).transpose(0, 2, 3, 1, 4)
    qf = qf.reshape(b * kvh * g, sq, dh)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3).reshape(b * kvh, sk, dh), g, axis=0)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3).reshape(b * kvh, sk, dh), g, axis=0)
    of = flash_attention_pallas(qf, kf, vf, causal=causal, blk_q=blk_q,
                                blk_k=blk_k, interpret=it)
    o = of.reshape(b, kvh, g, sq, dh).transpose(0, 3, 1, 2, 4)
    return o.reshape(b, sq, h, dh)


def fastscan_crude_topk(packed_codes, lut_flat, topk: int, *,
                        block_q: int = 64, block_n: int = 512,
                        interpret=None, want_crude: bool = True,
                        lut_scale=None, lut_offset=None):
    """The 4-bit fast-scan crude pass (DESIGN.md §12): phase 1 over
    nibble-packed codes (n, ceil(K/2)) uint8, unpacked in-VMEM via
    shift/mask; lut_flat must cover the even-padded K
    (``index.base.fastscan_kernel_operands`` / ``pad_luts_even``).
    Same outputs as ``batched_crude_topk``."""
    _check_faults("fastscan_crude_topk")
    it = _default_interpret() if interpret is None else interpret
    return fastscan_crude_topk_pallas(packed_codes, lut_flat, lut_scale,
                                      lut_offset, topk=topk,
                                      block_q=block_q, block_n=block_n,
                                      interpret=it, want_crude=want_crude)


def ivf_fastscan_crude_topk(packed_cand_codes, cand_ids, lut_flat,
                            topk: int, *, block_q: int = 8,
                            block_n: int = 128, interpret=None,
                            lut_scale=None, lut_offset=None):
    """The 4-bit fast-scan IVF slab crude pass: ``ivf_crude_topk`` over
    a nibble-packed candidate slab (nq, nc, ceil(K/2)) uint8 (see
    ``fastscan_crude_topk``)."""
    _check_faults("ivf_fastscan_crude_topk")
    it = _default_interpret() if interpret is None else interpret
    return ivf_fastscan_crude_topk_pallas(packed_cand_codes, cand_ids,
                                          lut_flat, lut_scale, lut_offset,
                                          topk=topk, block_q=block_q,
                                          block_n=block_n, interpret=it)


def pack_nibbles(codes, K: int):
    """Nibble-pack 4-bit codes two-per-byte along the codebook axis
    (the ``code_bits=4`` storage format) — re-export of
    ``core.encode.pack_nibbles`` at the kernel-ops surface."""
    from repro.core.encode import pack_nibbles as _pack
    return _pack(codes, K)


def unpack_nibbles(packed, K: int):
    """Inverse of ``pack_nibbles`` (exact round trip; drops the odd-K
    sentinel column) — re-export of ``core.encode.unpack_nibbles``."""
    from repro.core.encode import unpack_nibbles as _unpack
    return _unpack(packed, K)
