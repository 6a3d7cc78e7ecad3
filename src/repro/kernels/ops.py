"""Jit'd public wrappers around the Pallas kernels with automatic fallback
to the pure-jnp oracle for shapes/bitwidths the kernels don't tile
(3-bit codes, non-divisible shapes, scalar decode queries).

``dequant_matmul`` is the dispatch point for packed-offloaded MoE
execution (``models/moe.moe_apply_packed``, DESIGN.md §6): batch-1 decode
and 3-bit codes take the reference path on every backend; MXU-aligned
2/4/8-bit shapes take the fused Pallas kernel — compiled on a TPU,
interpreted on the CPU (``repro.kernels.resolve_interpret``)."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.dequant_matmul import (dequant_matmul_batched_pallas,
                                          dequant_matmul_pallas,
                                          dequant_matmul_slots_pallas)
from repro.kernels.flash_attention import flash_attention_pallas
from repro.quant.hqq import QTensor, _meta_dequantize, unpack_codes

KERNEL_BITS = (2, 4, 8)


def dequant_matmul(x, qt: QTensor, *, use_kernel=True):
    """x (M, K) @ dequant(qt) where qt quantizes a (K, N) weight."""
    assert len(qt.shape) == 2, "2-D weights (reshape heads first)"
    scale, zero = _meta_dequantize(qt)
    M, K = x.shape
    N = qt.shape[-1]
    if _batched_ok(use_kernel, qt.bits, M, N, K, qt.group_size):
        bm = 128 if M % 128 == 0 else 8
        return dequant_matmul_pallas(
            x, qt.packed, scale, zero, bits=qt.bits,
            group_size=qt.group_size, bm=bm)
    return ref.dequant_matmul_ref(x, qt.packed, scale, zero, bits=qt.bits,
                                  group_size=qt.group_size)


def _batched_ok(use_kernel, bits, M, N, K, group_size):
    """Shared kernel-eligibility gate (single-slice and batched paths)."""
    return (use_kernel and bits in KERNEL_BITS and M % 8 == 0
            and N % 128 == 0 and K % max(128, group_size) == 0)


def _dequant_rows(qt_stacked: QTensor, scale, zero):
    """Dequantize a (B, G, pg, N)-packed row stack to (B, K, N) f32."""
    B, G, _, N = qt_stacked.packed.shape
    q = unpack_codes(qt_stacked.packed, qt_stacked.bits,
                     qt_stacked.group_size).astype(jnp.float32)
    w = (q - zero.astype(jnp.float32)) * scale.astype(jnp.float32)
    return w.reshape(B, G * qt_stacked.group_size, N)


def dequant_matmul_batched(x, qt: QTensor, *, use_kernel=True):
    """x (B, M, K) @ dequant(qt[b]) per row, qt stacked (B, K, N) packed.

    ONE dispatch covers the whole batch of per-(token, k) expert matmuls
    of the vectorized packed MoE path (DESIGN.md §7) — the replacement
    for B separate :func:`dequant_matmul` calls.  Pallas batched kernel
    when shapes/bits tile; jnp batched reference otherwise (bitwise equal
    to the per-slice path on this backend — tested)."""
    assert len(qt.shape) == 3, "expect (B,)-stacked 2-D weights"
    scale, zero = _meta_dequantize(qt)
    B, M, K = x.shape
    N = qt.shape[-1]
    if _batched_ok(use_kernel, qt.bits, M, N, K, qt.group_size):
        bm = 128 if M % 128 == 0 else 8
        return dequant_matmul_batched_pallas(
            x, qt.packed, scale, zero, bits=qt.bits,
            group_size=qt.group_size, bm=bm)
    w = _dequant_rows(qt, scale, zero)
    return jnp.einsum("bmk,bkn->bmn", x.astype(jnp.float32), w)


def dequant_matmul_slots(x, qt: QTensor, slots, *, use_kernel=True):
    """x (B, M, K) @ dequant(qt[slots[b]]): serve a batch of matmuls by
    *slot index* into a stacked packed tier (S, K, N) without gathering
    it — the Pallas kernel reads each program's source block through a
    scalar-prefetched ``slots`` (B,) array (DESIGN.md §7).  Off-kernel
    shapes gather the (small) packed leaves and run the batched
    reference."""
    assert len(qt.shape) == 3, "expect (S,)-stacked 2-D weights"
    scale, zero = _meta_dequantize(qt)
    B, M, K = x.shape
    N = qt.shape[-1]
    if _batched_ok(use_kernel, qt.bits, M, N, K, qt.group_size):
        bm = 128 if M % 128 == 0 else 8
        return dequant_matmul_slots_pallas(
            x, qt.packed, scale, zero, slots, bits=qt.bits,
            group_size=qt.group_size, bm=bm)
    gathered = QTensor(qt.packed[slots], scale[slots], zero[slots], None,
                       qt.bits, qt.group_size, (B,) + tuple(qt.shape[1:]))
    w = _dequant_rows(gathered, gathered.scale, gathered.zero)
    return jnp.einsum("bmk,bkn->bmn", x.astype(jnp.float32), w)


def flash_attention(q, k, v, *, causal=True, window=None, q_offset=0,
                    use_kernel=True):
    BH, Sq, d = q.shape
    ok = (use_kernel and Sq % 8 == 0 and k.shape[1] % 128 == 0
          and d % 8 == 0)
    if ok:
        bq = 128 if Sq % 128 == 0 else 8
        return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                      bq=bq, q_offset=q_offset)
    return ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                   q_offset=q_offset)
