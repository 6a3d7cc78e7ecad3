"""Packed offloaded decode: vectorized overlap-pipelined stream vs the
PR-2 synchronous per-(token, k) data plane (DESIGN.md §7).

Three engine variants decode the same prompt over the same HQQ-packed
store, all bitwise-identical to the dequantized-model oracle (asserted):

* ``pr2_sync``   — the PR-2 baseline: unrolled per-(token, k) slot swaps
  + T*K separate dequant-matmul calls, staging serialized inside the
  per-block jitted program (``pipelined=False, vectorized=False``).
* ``vectorized`` — batched gather/scatter slot plans + one batched
  dequant-matmul dispatch per matrix, staging still synchronous
  (``pipelined=False``).
* ``pipelined``  — the default engine: vectorized plane + speculative
  staging dispatched asynchronously outside the jitted block, fencing
  only at the lookahead layer's ``acquire``.

Reported per variant: compile (first-generate) seconds, steady-state
decode tokens/s with p50/p95 per-token latency, and measured h2d
bytes/token + hit ratio.  The traffic counters must agree across
variants — the data-plane refactor changes *how* bytes move, never how
many.

A fourth scenario, ``speculative`` (DESIGN.md §11), reruns the
pipelined plane at a high cache hit ratio (cache 6/8 experts, ~0.9)
with token-level draft-and-verify decoding against a replay draft at
acceptance 1.0: one C = k+1 verify chunk emits k+1 tokens, so per-token
dispatch overhead and expert traffic amortize.  Output stays bitwise
the oracle's (asserted), generation h2d must not exceed the
non-speculative baseline's (asserted), and the full run asserts the
>= 1.3x decode-throughput acceptance bar.

Results persist to ``experiments/bench/offload_bench.json`` AND the
repo-root ``BENCH_offload.json`` so the perf trajectory is trackable
across PRs.

    PYTHONPATH=src python -m benchmarks.offload_bench [--smoke] [--trained]
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import numpy as np

from benchmarks.common import emit

from repro.configs import get_config
from repro.core.offload_engine import (OffloadEngine, generate_plain,
                                       quantize_for_offload)
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T

ROOT = Path(__file__).resolve().parents[1]

VARIANTS = {
    "pr2_sync": dict(pipelined=False, vectorized=False),
    "vectorized": dict(pipelined=False, vectorized=True),
    "pipelined": dict(pipelined=True, vectorized=True),
}


def run(smoke=False, trained=False, max_new=None, seed=0):
    cfg = get_config("tiny-moe")
    if trained:
        from benchmarks.common import get_trained_tiny_moe
        params, cfg = get_trained_tiny_moe()
    else:
        params = T.init_model(jax.random.key(seed), cfg)
    spec = cfg.offload
    max_new = max_new or (8 if smoke else 48)
    rng = np.random.default_rng(seed)
    prompt = rng.integers(1, cfg.vocab_size, (1, 12)).astype(np.int32)

    qdeq, _ = quantize_for_offload(params, cfg, spec)
    oracle = generate_plain(qdeq, cfg, prompt, max_new)

    # pre-warm the executables ALL variants share through the cfg-keyed
    # jit cache (embed/head, layerwise packed prefill): a distinct mode
    # compiles its own block programs but leaves the shared ones hot, so
    # each variant's first-generate time below reflects only its own
    # data-plane programs, not cache-warmup ordering
    warm = OffloadEngine(params, cfg, spec, quantized=True,
                         pipelined=False, vectorized=True, fused=False)
    warm.generate(prompt, max_new)

    results = []
    traffic = {}
    for name, kw in VARIANTS.items():
        import jax.numpy as jnp

        eng = OffloadEngine(params, cfg, spec, quantized=True, **kw)
        t0 = time.perf_counter()
        out, stats = eng.generate(prompt, max_new)  # compiles the variant
        t_compile = time.perf_counter() - t0
        assert (out == oracle).all(), f"{name}: diverged from oracle"
        traffic[name] = (stats.hits, stats.spec_hits, stats.demand_loads,
                         stats.spec_loads)
        # row fields come from the telemetry registry (the same snapshot
        # --metrics-json writes); the returned OffloadStats must agree
        # exactly — a drift here means the collector and the engine's own
        # accounting diverged (DESIGN.md §10)
        om = eng.metrics()["offload"]
        assert (om["hits"], om["spec_hits"], om["demand_loads"],
                om["spec_loads"]) == traffic[name], \
            f"{name}: registry drifted from OffloadStats: {om}"
        assert om["bytes_h2d"] == stats.bytes_h2d
        bpt = om["bytes_per_token"]
        # steady-state decode: time the jitted token loop alone (prefill
        # and pool-state init are identical across variants)
        dec = eng._decoder  # the packed-plane runtime Executor
        ps = dec.init_pool_state()
        logits, state, _ = dec.prefill(jnp.asarray(prompt),
                                       prompt.shape[1] + max_new + 4)
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        for _ in range(2):  # warm donation buffers
            logits, state, ps, _ = dec.decode(state, tok, ps)
        jax.block_until_ready(logits)
        lat_ms = []
        t0 = time.perf_counter()
        for _ in range(max_new):
            t1 = time.perf_counter()
            logits, state, ps, _ = dec.decode(state, tok, ps)
            jax.block_until_ready(logits)
            lat_ms.append((time.perf_counter() - t1) * 1e3)
        t_gen = time.perf_counter() - t0
        results.append({
            "name": "offload_bench", "variant": name,
            "max_new": max_new,
            "first_gen_s": round(t_compile, 3),  # variant's jit + 1 gen
            "decode_ms_per_token": round(t_gen / max_new * 1e3, 2),
            "p50_ms": round(float(np.percentile(lat_ms, 50)), 2),
            "p95_ms": round(float(np.percentile(lat_ms, 95)), 2),
            "tok_s": round(max_new / t_gen, 2),
            "bytes_per_token": round(bpt, 1),
            "hit_ratio": round(stats.hit_ratio, 4),
        })
        print(f"[offload_bench] {name:10s}: {max_new / t_gen:8.2f} tok/s "
              f"decode ({t_gen / max_new * 1e3:6.1f} ms/token, "
              f"p50/p95 {np.percentile(lat_ms, 50):.1f}/"
              f"{np.percentile(lat_ms, 95):.1f}ms, first gen "
              f"{t_compile:6.1f}s, {bpt / 1e3:.1f}KB/token h2d, "
              f"hit_ratio={stats.hit_ratio:.3f})")
    assert len(set(traffic.values())) == 1, \
        f"variants disagree on transfer counters: {traffic}"
    base = next(r for r in results if r["variant"] == "pr2_sync")
    pipe = next(r for r in results if r["variant"] == "pipelined")
    speedup = pipe["tok_s"] / base["tok_s"]
    compile_speedup = base["first_gen_s"] / max(1e-9, pipe["first_gen_s"])
    print(f"[offload_bench] decode speedup (pipelined vs pr2_sync): "
          f"{speedup:.2f}x; first-generate (compile) {compile_speedup:.2f}x "
          f"faster")
    results.append({"name": "offload_bench", "variant": "summary",
                    "speedup": round(speedup, 3),
                    "compile_speedup": round(compile_speedup, 3)})

    # ------------------------------------------------------------------
    # speculative scenario (DESIGN.md §11): pipelined plane, cache 6/8
    # experts (hit_ratio ~0.9), replay draft at acceptance 1.0
    import dataclasses

    from repro.core.draft import ReplayDraft

    k = 4
    spec_hi = dataclasses.replace(spec, cache_size=6)
    ref = np.concatenate([prompt[0], oracle[0]])  # same packed weights:
    # expert/attn bits are unchanged, so the dequantized oracle is too
    eng = OffloadEngine(params, cfg, spec_hi, quantized=True)

    def timed_gen(**kw):
        out, stats = eng.generate(prompt, max_new, **kw)  # compile pass
        assert (out == oracle).all(), "speculative scenario: diverged"
        t0 = time.perf_counter()
        out, stats = eng.generate(prompt, max_new, **kw)
        t = time.perf_counter() - t0
        assert (out == oracle).all(), "speculative scenario: diverged"
        return t, stats

    t_base, s_base = timed_gen()
    mk = lambda: ReplayDraft(ref, vocab_size=cfg.vocab_size)  # noqa: E731
    t_spec, s_spec = timed_gen(draft=mk(), num_draft_tokens=k)
    assert s_spec.bytes_h2d <= s_base.bytes_h2d, \
        f"speculation increased generation h2d at acceptance 1.0: " \
        f"{s_spec.bytes_h2d} > {s_base.bytes_h2d}"
    sm = eng.obs.snapshot()["spec"]
    spec_speedup = t_base / t_spec
    for variant, t, stats in (("spec_baseline", t_base, s_base),
                              ("speculative", t_spec, s_spec)):
        results.append({
            "name": "offload_bench", "variant": variant,
            "max_new": max_new, "num_draft_tokens": 0 if t is t_base else k,
            "decode_ms_per_token": round(t / max_new * 1e3, 2),
            "tok_s": round(max_new / t, 2),
            "bytes_per_token": round(stats.bytes_h2d / max(1, stats.n_tokens), 1),
            "hit_ratio": round(stats.hit_ratio, 4),
        })
        print(f"[offload_bench] {variant:13s}: {max_new / t:8.2f} tok/s "
              f"decode ({t / max_new * 1e3:6.1f} ms/token, "
              f"hit_ratio={stats.hit_ratio:.3f}, "
              f"h2d={stats.bytes_h2d / 1e6:.2f}MB)")
    print(f"[offload_bench] speculative speedup (k={k}, acceptance "
          f"{sm['acceptance_rate']:.2f}): {spec_speedup:.2f}x over "
          f"non-speculative pipelined at hit_ratio="
          f"{s_base.hit_ratio:.3f}")
    results.append({"name": "offload_bench", "variant": "spec_summary",
                    "num_draft_tokens": k,
                    "acceptance_rate": round(sm["acceptance_rate"], 3),
                    "hit_ratio": round(s_base.hit_ratio, 4),
                    "spec_speedup": round(spec_speedup, 3)})
    if not smoke:
        assert spec_speedup >= 1.3, \
            f"speculative decode speedup {spec_speedup:.2f}x below the " \
            f"1.3x acceptance bar"

    # ------------------------------------------------------------------
    # router top-k ablation (serve --top-k-override): routing each token
    # to 1 expert instead of the arch default shrinks the per-token
    # expert working set, so the LRU misses less and the offloaded
    # decode streams fewer bytes — the traffic drop the CLI flag buys
    from repro.launch.serve import resolve_top_k

    assert cfg.moe.top_k > 1, "top-k ablation needs a multi-expert router"
    eng_k1 = OffloadEngine(params, resolve_top_k(cfg, 1), spec,
                           quantized=True)
    _, s_k1 = eng_k1.generate(prompt, max_new)
    bpt_k1 = s_k1.bytes_h2d / max(1, s_k1.n_tokens)
    bpt_base = pipe["bytes_per_token"]  # same engine class/spec/prompt
    assert bpt_k1 < bpt_base, \
        f"k=1 routing must cut h2d traffic: {bpt_k1:.0f} >= {bpt_base:.0f}"
    results.append({
        "name": "offload_bench", "variant": "top_k_override",
        "top_k": 1, "arch_top_k": cfg.moe.top_k, "max_new": max_new,
        "bytes_per_token": round(bpt_k1, 1),
        "baseline_bytes_per_token": round(bpt_base, 1),
        "h2d_savings_ratio": round(bpt_base / max(1e-9, bpt_k1), 3),
        "hit_ratio": round(s_k1.hit_ratio, 4),
    })
    print(f"[offload_bench] top_k_override: k=1 h2d "
          f"{bpt_k1 / 1e3:.1f}KB/token vs k={cfg.moe.top_k} "
          f"{bpt_base / 1e3:.1f}KB/token "
          f"({bpt_base / max(1e-9, bpt_k1):.2f}x less traffic)")

    emit(results, "offload_bench")
    (ROOT / "BENCH_offload.json").write_text(json.dumps(results, indent=1))
    print("[offload_bench] wrote BENCH_offload.json")
    if smoke:
        # smoke asserts structure, not margins (CI machines are noisy) —
        # but the vectorized plane must at least not be slower than the
        # unrolled one by more than jitter
        assert speedup > 0.5, "smoke: pipelined path unreasonably slow"
        assert spec_speedup > 0.5, "smoke: speculative path unreasonably slow"
        print("[offload_bench] smoke OK")
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny run for CI (asserts parity + sanity)")
    ap.add_argument("--trained", action="store_true",
                    help="use the trained tiny-moe artifact (realistic "
                         "routing locality; trains + caches on first use)")
    ap.add_argument("--max-new", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()
    run(smoke=args.smoke, trained=args.trained, max_new=args.max_new,
        seed=args.seed)


if __name__ == "__main__":
    main()
