"""Kernel micro-benchmarks: Pallas kernels and the jnp reference paths.

On a TPU the Pallas rows time the compiled kernels (``*_compiled``); on
the CPU they run in interpret mode (``*_interp``) and `us_per_call`
measures the Python interpreter loop, reported for completeness only
(derived column carries the analytic FLOPs)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref, resolve_interpret
from repro.quant import hqq

from benchmarks.common import emit, timeit


def run(quick=False):
    rows = []
    if resolve_interpret():
        mode, how = "interp", "interpret-mode (CPU emulation)"
    else:
        mode, how = "compiled", f"compiled ({jax.devices()[0].device_kind})"
    # --- dequant matmul ---
    M, K, N = (32, 256, 128) if quick else (64, 1024, 512)
    w = jax.random.normal(jax.random.key(0), (K, N)) * 0.05
    x = jax.random.normal(jax.random.key(1), (M, K))
    for bits in (2, 4, 8):
        qt = hqq.quantize(w, bits, group_size=64, scale_group=None)
        scale, zero = hqq._meta_dequantize(qt)
        flops = 2 * M * K * N

        jref = jax.jit(lambda xx, p=qt.packed, s=scale, z=zero:
                       ref.dequant_matmul_ref(xx, p, s, z, bits=bits,
                                              group_size=64))
        us, _ = timeit(jref, x)
        rows.append({"name": f"dequant_matmul_ref_{bits}bit_jit",
                     "us_per_call": f"{us:.1f}",
                     "derived": f"gflops={flops/us/1e3:.2f}"})
        if not quick:
            us_k, _ = timeit(
                lambda xx: ops.dequant_matmul(xx, qt), x,
                warmup=1, iters=1)
            rows.append({"name": f"dequant_matmul_pallas_{bits}bit_{mode}",
                         "us_per_call": f"{us_k:.0f}", "derived": how})

    # --- flash attention ---
    BH, BKV, S, d = (4, 2, 256, 64) if quick else (8, 2, 1024, 64)
    q = jax.random.normal(jax.random.key(2), (BH, S, d))
    k = jax.random.normal(jax.random.key(3), (BKV, S, d))
    v = jax.random.normal(jax.random.key(4), (BKV, S, d))
    flops = 4 * BH * S * S * d
    jref = jax.jit(lambda a, b, c: ref.flash_attention_ref(a, b, c,
                                                           causal=True))
    us, _ = timeit(jref, q, k, v)
    rows.append({"name": "flash_attention_ref_jit",
                 "us_per_call": f"{us:.1f}",
                 "derived": f"gflops={flops/us/1e3:.2f}"})
    if not quick:
        us_k, _ = timeit(
            lambda a, b, c: ops.flash_attention(a, b, c, causal=True),
            q, k, v, warmup=1, iters=1)
        rows.append({"name": f"flash_attention_pallas_{mode}",
                     "us_per_call": f"{us_k:.0f}", "derived": how})

    # --- model-level chunked attention (production jnp path) ---
    from repro.models.layers import attention_core
    B, S2, Hkv, G, hd = 2, 512, 2, 2, 64
    qq = jax.random.normal(jax.random.key(5), (B, S2, Hkv * G, hd))
    kk = jax.random.normal(jax.random.key(6), (B, S2, Hkv, hd))
    vv = jax.random.normal(jax.random.key(7), (B, S2, Hkv, hd))
    pos = jnp.arange(S2, dtype=jnp.int32)
    f = jax.jit(lambda a, b, c: attention_core(a, b, c, pos, pos,
                                               causal=True, window=None))
    us, _ = timeit(f, qq, kk, vv)
    rows.append({"name": "model_chunked_attention_jit",
                 "us_per_call": f"{us:.1f}",
                 "derived": f"B{B}xS{S2}xH{Hkv*G}"})
    emit(rows, "kernels")
    return rows


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", "--quick", action="store_true",
                    dest="smoke",
                    help="reduced shapes, jnp reference paths only "
                         "(CI smoke)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run(quick=args.smoke)
