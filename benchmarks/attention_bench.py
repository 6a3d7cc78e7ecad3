"""Decode-attention benchmark: dense ring vs ragged paged (DESIGN.md §9).

The dense slotted plane pays O(n_slots x slot_len) attention every decode
step no matter how much context is actually live; the paged plane gathers
only the live page horizon, so its cost follows live tokens.  This bench
measures exactly that:

* ``decode_scaling`` rows — one batched decode-step attention at a fixed
  slot width, with the batch's live context swept from 1/8 of the slot to
  full: the dense time stays flat (it cannot see liveness), the ragged
  time scales down with the live fraction.
* ``worklist`` rows — the ragged Pallas kernel's grid size (work-list
  length) for mixed per-row lengths, with and without a sliding window:
  O(total live pages), not O(batch x table width) — including the pages
  the window lets the kernel skip outright.

Results print to stdout and persist machine-readable to
``experiments/bench/attention_bench.json`` AND the repo-root
``BENCH_attention.json`` (the perf-trajectory snapshot CI prints).

    PYTHONPATH=src python -m benchmarks.attention_bench [--smoke]
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit

from repro.kernels import ragged_attention as RA
from repro.launch.compile_cache import enable_compile_cache
from repro.models.layers import attention_core

ROOT = Path(__file__).resolve().parents[1]


def _bucket(n):
    w = 1
    while w < n:
        w *= 2
    return w


def _make_layouts(rng, lens, W, ps, Hkv, hd):
    """Dense ring + paged pool carrying the same live KV entries."""
    B = len(lens)
    T = W // ps
    kd = np.zeros((B, W, Hkv, hd), np.float32)
    vd = np.zeros((B, W, Hkv, hd), np.float32)
    posd = np.full((B, W), -1, np.int32)
    kp = np.zeros((B * T, ps, Hkv, hd), np.float32)
    vp = np.zeros((B * T, ps, Hkv, hd), np.float32)
    ppos = np.full((B * T, ps), -1, np.int32)
    pages = np.full((B, T), -1, np.int32)
    nxt = 0
    for b, n in enumerate(lens):
        n_pages = -(-int(n) // ps)
        for o in range(n_pages):
            pages[b, o] = nxt
            nxt += 1
        k = rng.standard_normal((int(n), Hkv, hd)).astype(np.float32)
        v = rng.standard_normal((int(n), Hkv, hd)).astype(np.float32)
        kd[b, :n], vd[b, :n], posd[b, :n] = k, v, np.arange(n)
        for p_ in range(int(n)):
            pid = pages[b, p_ // ps]
            kp[pid, p_ % ps], vp[pid, p_ % ps] = k[p_], v[p_]
            ppos[pid, p_ % ps] = p_
    return (jnp.asarray(kd), jnp.asarray(vd), jnp.asarray(posd)), \
        (jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(ppos),
         jnp.asarray(pages))


def _time(fn, *args, iters=30):
    fn(*args).block_until_ready()  # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    out.block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e3  # ms


def run(smoke=False, seed=0):
    rng = np.random.default_rng(seed)
    if smoke:
        B, W, ps, Hkv, G, hd, iters = 2, 128, 16, 2, 2, 32, 5
    else:
        B, W, ps, Hkv, G, hd, iters = 4, 512, 32, 4, 2, 64, 30
    H = Hkv * G
    window = None

    dense_fn = jax.jit(lambda q, k, v, qp, kp_: attention_core(
        q, k, v, qp, kp_, causal=True, window=window, q_chunk=1))
    ragged_fn = jax.jit(lambda q, kp, vp, pp, pg, qp:
                        RA.ragged_attention_reference(
                            q, kp, vp, pp, pg, qp, window=window, q_chunk=1))

    results = []
    print(f"[attention_bench] decode-step attention, {B} slots x "
          f"slot_len {W} (page {ps}):")
    for frac in (0.125, 0.25, 0.5, 1.0):
        lens = np.full((B,), max(1, int(W * frac)), np.int64)
        (kd, vd, posd), (kp, vp, ppos, pages) = _make_layouts(
            rng, lens, W, ps, Hkv, hd)
        q = jnp.asarray(rng.standard_normal((B, 1, H, hd)), jnp.float32)
        qpos = jnp.asarray(lens[:, None].astype(np.int32))  # next position
        t_dense = _time(dense_fn, q, kd, vd, qpos, posd, iters=iters)
        width = min(_bucket(-(-int(lens.max()) // ps)), W // ps)
        t_ragged = _time(ragged_fn, q, kp, vp, ppos,
                         pages[:, :width], qpos, iters=iters)
        parity = np.array_equal(
            np.asarray(dense_fn(q, kd, vd, qpos, posd)),
            np.asarray(ragged_fn(q, kp, vp, ppos, pages, qpos)))
        assert parity, "paged attention diverged from the dense ring"
        row = {"name": "attention_bench", "scenario": "decode_scaling",
               "slots": B, "slot_len": W, "page": ps,
               "live_frac": frac, "live_tokens": int(lens.sum()),
               "table_width_pages": width,
               "dense_ms": round(t_dense, 3),
               "ragged_ms": round(t_ragged, 3),
               "speedup": round(t_dense / max(1e-9, t_ragged), 3),
               "bitwise_parity_full_width": True}
        results.append(row)
        print(f"  live {frac:5.3f} ({int(lens[0]):4d} tok/row): dense "
              f"{t_dense:7.3f}ms  ragged {t_ragged:7.3f}ms "
              f"({row['speedup']:.2f}x, width {width}p)")

    # ragged kernel grid scaling: mixed lengths, with / without a window
    lens = rng.integers(1, W, B).astype(np.int64)
    _, (kp, vp, ppos, pages) = _make_layouts(rng, lens, W, ps, Hkv, hd)
    q_lo = q_hi = (lens - 1).astype(np.int32)
    for win in (None, max(ps, W // 8)):
        wrow, _, wflags = RA.build_page_worklist(
            np.asarray(pages), lens, q_lo, q_hi, ps, window=win)
        n_live = int(wflags[:, 2].sum())
        results.append({"name": "attention_bench", "scenario": "worklist",
                        "window": win, "live_tokens": int(lens.sum()),
                        "live_pages": n_live,
                        "dense_grid_pages": B * (W // ps)})
        print(f"[attention_bench] kernel grid (window={win}): {n_live} "
              f"pages visited vs {B * (W // ps)} dense "
              f"({int(lens.sum())} live tokens)")
        assert n_live <= sum(-(-int(n) // ps) for n in lens)

    # smoke also exercises the Pallas kernel itself at a tiny shape
    if smoke:
        wl = RA.build_page_worklist(np.asarray(pages), lens, q_lo, q_hi, ps)
        qk = jnp.asarray(rng.standard_normal((B, 1, H, hd)), jnp.float32)
        qp = jnp.asarray(lens[:, None].astype(np.int32) - 1)
        out = RA.ragged_attention(qk, kp, vp, ppos, pages, qp, worklist=wl)
        ref = RA.ragged_attention_reference(qk, kp, vp, ppos, pages, qp,
                                            q_chunk=1)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        print("[attention_bench] smoke OK (pallas kernel parity)")

    emit(results, "attention_bench")
    (ROOT / "BENCH_attention.json").write_text(json.dumps(results, indent=1))
    print(f"[attention_bench] wrote BENCH_attention.json "
          f"({len(results)} rows)")
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for CI (seconds, asserts parity)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()
    run(smoke=args.smoke, seed=args.seed)


if __name__ == "__main__":
    main()
