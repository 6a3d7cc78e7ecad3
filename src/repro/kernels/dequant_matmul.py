"""Pallas TPU kernel: fused group-dequantize (HQQ packed 2/4/8-bit) x matmul.

This is the paper's perf-critical compute adapted to TPU: on GPU the HQQ
reference dequantizes expert weights with CUDA kernels before cuBLAS; on
TPU we instead keep the weight **packed in VMEM** and unpack/dequantize
blockwise right before feeding the MXU, so HBM traffic is the *quantized*
bytes (the whole point of compression-for-offloading, section 3.3: "model
compression has a natural synergy with offloading").

Tiling: grid (M/bm, N/bn, K/bk), K innermost for accumulation.  ``bk`` must
be a multiple of ``group_size`` so each K-block covers whole quant groups;
block shapes default to MXU-aligned (128) multiples.  The f32 accumulator
lives in the output block (revisited across the K grid dimension — Pallas
keeps it in VMEM).

3-bit codes don't unpack with static strides (8 codes span 3 bytes), so
3-bit uses the jnp reference path (``ops.dequant_matmul`` dispatches);
noted in DESIGN.md §6.

This kernel is the compute path of packed-offloaded MoE execution:
``models/moe.moe_apply_packed`` feeds each served pool slot's packed
weights through ``ops.dequant_matmul`` (DESIGN.md §6).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret
from repro.quant.hqq import unpack_codes


def _kernel(x_ref, p_ref, s_ref, z_ref, o_ref, *, bits, group_size):
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]  # (bm, bk)
    packed = p_ref[...]  # (bk//g, g*bits//8, bn)
    scale = s_ref[...].astype(jnp.float32)  # (bk//g, 1, bn)
    zero = z_ref[...].astype(jnp.float32)
    # widen before unpacking: Mosaic has no uint8 -> f32 cast, and the
    # shifts/masks are exact in int32 (the store keeps the uint8 format)
    q = unpack_codes(packed.astype(jnp.int32), bits,
                     group_size).astype(jnp.float32)
    w = (q - zero) * scale  # (bk//g, g, bn)
    w = w.reshape(x.shape[1], -1)  # (bk, bn)
    o_ref[...] += jnp.dot(x.astype(jnp.float32), w,
                          preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bits", "group_size", "bm",
                                             "bn", "bk", "interpret"))
def dequant_matmul_pallas(x, packed, scale, zero, *, bits, group_size,
                          bm=128, bn=128, bk=128, interpret=None):
    """x: (M, K) @ packed W (G, g*bits//8, N) -> (M, N) f32."""
    M, K = x.shape
    G, pg, N = packed.shape
    assert G * group_size == K
    bm = min(bm, M)
    bn = min(bn, N)
    bk = min(bk, K)
    assert bk % group_size == 0 and K % bk == 0 and M % bm == 0 and N % bn == 0
    gb = bk // group_size  # groups per K block
    n_k = K // bk

    grid = (M // bm, N // bn, n_k)
    return pl.pallas_call(
        functools.partial(_kernel, bits=bits, group_size=group_size),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((gb, pg, bn), lambda i, j, k: (k, 0, j)),
            pl.BlockSpec((gb, 1, bn), lambda i, j, k: (k, 0, j)),
            pl.BlockSpec((gb, 1, bn), lambda i, j, k: (k, 0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        interpret=resolve_interpret(interpret),
    )(x, packed, scale, zero)


# ----------------------------------------------------------------------
# Batched / slot-gather variants (DESIGN.md §7): the compute side of the
# vectorized packed-expert data plane.  One kernel launch covers every
# (token, k) pair of an MoE layer's batch instead of T*K separate calls.
def _batched_kernel(x_ref, p_ref, s_ref, z_ref, o_ref, *, bits, group_size):
    k_step = pl.program_id(3)

    @pl.when(k_step == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[0]  # (bm, bk)
    packed = p_ref[0]  # (gb, g*bits//8, bn)
    scale = s_ref[0].astype(jnp.float32)
    zero = z_ref[0].astype(jnp.float32)
    q = unpack_codes(packed.astype(jnp.int32), bits,
                     group_size).astype(jnp.float32)
    w = ((q - zero) * scale).reshape(x.shape[1], -1)  # (bk, bn)
    o_ref[0] += jnp.dot(x.astype(jnp.float32), w,
                        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bits", "group_size", "bm",
                                             "bn", "bk", "interpret"))
def dequant_matmul_batched_pallas(x, packed, scale, zero, *, bits,
                                  group_size, bm=128, bn=128, bk=128,
                                  interpret=None):
    """x (B, M, K) @ per-row packed W (B, G, g*bits//8, N) -> (B, M, N)."""
    B, M, K = x.shape
    _, G, pg, N = packed.shape
    assert G * group_size == K
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert bk % group_size == 0 and K % bk == 0 and M % bm == 0 \
        and N % bn == 0
    gb = bk // group_size
    grid = (B, M // bm, N // bn, K // bk)
    return pl.pallas_call(
        functools.partial(_batched_kernel, bits=bits, group_size=group_size),
        out_shape=jax.ShapeDtypeStruct((B, M, N), jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda b, i, j, k: (b, i, k)),
            pl.BlockSpec((1, gb, pg, bn), lambda b, i, j, k: (b, k, 0, j)),
            pl.BlockSpec((1, gb, 1, bn), lambda b, i, j, k: (b, k, 0, j)),
            pl.BlockSpec((1, gb, 1, bn), lambda b, i, j, k: (b, k, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda b, i, j, k: (b, i, j)),
        interpret=resolve_interpret(interpret),
    )(x, packed, scale, zero)


def _slots_kernel(slots_ref, x_ref, p_ref, s_ref, z_ref, o_ref, *, bits,
                  group_size):
    del slots_ref  # consumed by the index maps (scalar prefetch)
    _batched_kernel(x_ref, p_ref, s_ref, z_ref, o_ref, bits=bits,
                    group_size=group_size)


@functools.partial(jax.jit, static_argnames=("bits", "group_size", "bm",
                                             "bn", "bk", "interpret"))
def dequant_matmul_slots_pallas(x, packed, scale, zero, slots, *, bits,
                                group_size, bm=128, bn=128, bk=128,
                                interpret=None):
    """x (B, M, K) @ dequant(W[slots[b]]) -> (B, M, N) where the packed
    weight tier W (S, G, g*bits//8, N) stays whole: ``slots`` (B,) int32
    rides in as a scalar-prefetch argument and the *index maps* pick each
    program's source block, so the gather happens inside the kernel's DMA
    schedule — no gathered copy of the packed tier is ever materialized
    (the slot-serving read of the vectorized expert pool, DESIGN.md §7).
    """
    B, M, K = x.shape
    S, G, pg, N = packed.shape
    assert G * group_size == K
    assert slots.shape == (B,)
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert bk % group_size == 0 and K % bk == 0 and M % bm == 0 \
        and N % bn == 0
    gb = bk // group_size
    grid = (B, M // bm, N // bn, K // bk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda b, i, j, k, sl: (b, i, k)),
            pl.BlockSpec((1, gb, pg, bn),
                         lambda b, i, j, k, sl: (sl[b], k, 0, j)),
            pl.BlockSpec((1, gb, 1, bn),
                         lambda b, i, j, k, sl: (sl[b], k, 0, j)),
            pl.BlockSpec((1, gb, 1, bn),
                         lambda b, i, j, k, sl: (sl[b], k, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda b, i, j, k, sl: (b, i, j)),
    )
    return pl.pallas_call(
        functools.partial(_slots_kernel, bits=bits, group_size=group_size),
        out_shape=jax.ShapeDtypeStruct((B, M, N), jnp.float32),
        grid_spec=grid_spec,
        interpret=resolve_interpret(interpret),
    )(slots.astype(jnp.int32), x, packed, scale, zero)
