"""Serving benchmarks: continuous vs static batching throughput, and
chunked-prefill decode-latency under mixed long-prompt traffic.

Workload 1 (throughput): N requests with mixed prompt lengths and mixed
output budgets, all backlogged at t=0 (the heavy-traffic regime the
ROADMAP targets).  The static baseline is the seed's serving shape —
FCFS groups of ``max_slots`` requests through ``ServeEngine.serve_batch``,
every group holding all its slots until the longest member finishes.  The
continuous engine releases a slot the step its request finishes and
admits the next request immediately, so short requests stop serialising
behind long ones.

Workload 2 (latency, DESIGN.md §8): short interactive requests decode
while LONG prompts arrive into freed slots.  Unchunked admission
prefills a whole long prompt inside one engine step, so every in-flight
decode stalls behind it — the head-of-line block shows up directly in
the p95 *inter-token latency* of the decode tokens emitted around the
admission.  With ``--prefill-chunk`` the prompt is packed into budgeted
chunks across steps and decode latency stays flat.  Generated tokens
are asserted identical (greedy decoding: chunking never changes a
logit bit, so argmax streams match token for token), and on the packed
plane the h2d transfer counters must agree exactly (prefill chunks
stream from the host store — zero pool traffic).

Workloads 5-6 (DESIGN.md §13): shared-system-prompt TTFT with the
prefix cache on vs off (hits must cut TTFT >= 2x), and an overloaded
pool where optimistic admission + host-swap preemption drains the same
backlog faster than the stall-and-serialize discipline — both with
bitwise token parity.

Results print to stdout and persist machine-readable to
``experiments/bench/serve_bench.json`` AND the repo-root
``BENCH_serve.json`` (tokens/s, p50/p95 inter-token latency, h2d bytes,
hit ratio), so the perf trajectory is trackable across PRs.

    PYTHONPATH=src python -m benchmarks.serve_bench [--smoke] [--trained]
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
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T
from repro.serving.engine import ContinuousEngine, Request, ServeEngine
from repro.serving.sampler import SamplerConfig
from repro.serving.scheduler import ExpertOverlapPolicy

ROOT = Path(__file__).resolve().parents[1]


def make_workload(cfg, n_requests, seed=0, smoke=False):
    """Interactive-traffic mix: mostly short replies, a tail of long
    generations (what makes static batching serialise short requests
    behind long ones).  Prompt lengths come from a small discrete set so
    per-length prefill compilation stays bounded."""
    rng = np.random.default_rng(seed)
    lengths = (4, 8) if smoke else (8, 16, 24, 32)
    short, long_ = ((2, 8), (8, 12)) if smoke else ((4, 16), (48, 64))
    reqs = []
    for _ in range(n_requests):
        s = int(rng.choice(lengths))
        prompt = rng.integers(1, cfg.vocab_size, s).astype(np.int32)
        lo, hi = short if rng.random() < 0.75 else long_
        reqs.append((prompt, int(rng.integers(lo, hi + 1))))
    return reqs


def run_static(eng, workload, max_slots):
    """FCFS groups of ``max_slots`` through the static engine."""
    toks = 0
    for i in range(0, len(workload), max_slots):
        group = [Request(p, m) for p, m in workload[i: i + max_slots]]
        for r in eng.serve_batch(group):
            toks += len(r.completed)
    return toks


def run_continuous(eng, workload):
    """Submit + drain one pass of the workload; returns (tokens, steps)
    generated by THIS pass.  The engine is shared between warmup and
    timed passes — jit caches live on the engine's closures, so a fresh
    engine would recompile inside the timed window.  (EOS semantics match
    ServeEngine.serve_batch, so both paths generate the same workload.)"""
    t_before = eng.stats()["tokens"]
    s_before = eng.step_count
    d_before = len(eng.sched.finished)
    for p, m in workload:
        eng.submit(p, m)
    eng.run(max_steps=100_000)
    assert len(eng.sched.finished) - d_before == len(workload), \
        "continuous engine dropped requests"
    return eng.stats()["tokens"] - t_before, eng.step_count - s_before


# ----------------------------------------------------------------------
# workload 2: chunked prefill vs whole-prompt admission latency
def make_latency_workload(cfg, max_slots, smoke=False, seed=0):
    """Short decode-heavy requests with long prompts behind them (FCFS
    order): the first ``max_slots`` shorts fill the slots and decode;
    every long then admits into a freed slot while the other rows are
    mid-stream — the head-of-line scenario."""
    rng = np.random.default_rng(seed)
    if smoke:
        n_short, short_len, short_news = 4, 6, (5, 9)
        n_long, long_len, long_new = 1, 24, 3
    else:
        # long prompts must dwarf a decode step (~35x here) for the
        # head-of-line block to be visible, and enough of them must
        # arrive that the stalled decode steps cross the p95 threshold;
        # short output budgets are STAGGERED so slots free one at a time
        # and every long admits into a still-decoding batch
        n_short, short_len, short_news = 6, 8, (10, 26)
        n_long, long_len, long_new = 4, 120, 2
    shorts = [(rng.integers(1, cfg.vocab_size, short_len).astype(np.int32),
               int(rng.integers(*short_news))) for _ in range(n_short)]
    longs = [(rng.integers(1, cfg.vocab_size, long_len).astype(np.int32),
              long_new) for _ in range(n_long)]
    out = shorts[:max_slots]
    rest = shorts[max_slots:]
    for i in range(max(len(longs), len(rest))):
        if i < len(longs):
            out.append(longs[i])
        if i < len(rest):
            out.append(rest[i])
    return out


def _drive_latency(eng, workload):
    """Run one pass recording per-step wall time; every DECODE token
    emitted in a step is charged that step's duration (its inter-token
    latency — the gap the request's consumer observed).  First tokens
    (prefill completion) are excluded: they measure time-to-first-token,
    not inter-token stall."""
    reqs = [eng.submit(p, m) for p, m in workload]
    samples_ms = []
    t0_all = time.perf_counter()
    while eng.sched.has_waiting or eng.sched.n_running:
        before = [len(r.generated) for r in reqs]
        t0 = time.perf_counter()
        eng.step()
        dt_ms = (time.perf_counter() - t0) * 1e3
        decode_emits = sum(1 for r, b in zip(reqs, before)
                           if len(r.generated) > b and b > 0)
        samples_ms += [dt_ms] * decode_emits
        assert eng.step_count < 100_000
    wall = time.perf_counter() - t0_all
    assert all(r.state == "finished" for r in reqs)
    return samples_ms, [r.generated for r in reqs], wall, eng


def run_chunked_latency(params, cfg, *, chunk, smoke=False, offload=None,
                        max_slots=2, slot_len=None, seed=0):
    """Measure decode inter-token latency: unchunked vs chunked
    admission over the identical workload/engine configuration.
    Returns the result row (and asserts token parity + packed-plane
    counter identity)."""
    slot_len = slot_len or (48 if smoke else 128)
    workload = make_latency_workload(cfg, max_slots, smoke=smoke, seed=seed)

    def make_engine(prefill_chunk):
        return ContinuousEngine(
            None if offload is not None else params, cfg,
            max_slots=max_slots, slot_len=slot_len, eos_id=None,
            prefill_chunk=prefill_chunk, offload=offload)

    results = {}
    counters = {}
    for name, c in (("unchunked", None), ("chunked", chunk)):
        _drive_latency(make_engine(c), workload)   # warmup: compile shapes
        samples, toks, wall, eng = _drive_latency(make_engine(c), workload)
        n_tok = sum(len(t) for t in toks)
        results[name] = {
            "samples": samples, "tokens": toks,
            "tok_s": n_tok / wall,
            "p50_ms": float(np.percentile(samples, 50)),
            "p95_ms": float(np.percentile(samples, 95)),
        }
        if offload is not None:
            # each engine owns its pool state, so the timed engine's
            # counters are exactly this pass's traffic
            s = eng.stats()
            counters[name] = {
                "h2d_bytes": s["offload_bytes_h2d"],
                "demand_loads": s["offload_demand_loads"],
                "spec_loads": s["offload_spec_loads"],
                "hit_ratio": s["offload_hits"] / max(
                    1, s["offload_hits"] + s["offload_demand_loads"]),
            }
    if offload is not None:
        assert counters["chunked"] == counters["unchunked"], \
            f"chunking changed packed h2d counters: {counters}"
    assert results["chunked"]["tokens"] == results["unchunked"]["tokens"], \
        "chunked prefill changed generated tokens"
    row = {
        "name": "serve_bench",
        "scenario": ("chunked_prefill_packed" if offload is not None
                     else "chunked_prefill"),
        "prefill_chunk": chunk, "max_slots": max_slots,
        "slot_len": slot_len,
        "unchunked_tok_s": round(results["unchunked"]["tok_s"], 2),
        "chunked_tok_s": round(results["chunked"]["tok_s"], 2),
        "unchunked_p50_ms": round(results["unchunked"]["p50_ms"], 2),
        "chunked_p50_ms": round(results["chunked"]["p50_ms"], 2),
        "unchunked_p95_ms": round(results["unchunked"]["p95_ms"], 2),
        "chunked_p95_ms": round(results["chunked"]["p95_ms"], 2),
        "p95_speedup": round(results["unchunked"]["p95_ms"]
                             / max(1e-9, results["chunked"]["p95_ms"]), 3),
        "token_parity": True,
    }
    if offload is not None:
        row.update({"h2d_bytes": counters["chunked"]["h2d_bytes"],
                    "hit_ratio": round(counters["chunked"]["hit_ratio"], 4),
                    "counters_identical": True})
    tag = "packed " if offload is not None else ""
    print(f"[serve_bench] {tag}inter-token p50/p95: "
          f"unchunked {row['unchunked_p50_ms']:.1f}/"
          f"{row['unchunked_p95_ms']:.1f}ms -> chunked "
          f"{row['chunked_p50_ms']:.1f}/{row['chunked_p95_ms']:.1f}ms "
          f"(p95 {row['p95_speedup']:.2f}x)")
    return row


# ----------------------------------------------------------------------
# workload 3: paged KV vs dense ring under mixed long-prompt traffic
def run_paged_kv(params, cfg, *, smoke=False, max_slots=4, seed=0,
                 kv_page=None):
    """Dense slotted KV vs block-paged KV (DESIGN.md §9) over the same
    mixed long-prompt workload at a *generous* slot width — the
    realistic setting where slots are provisioned for the worst case.
    The dense plane pays slot_len of attention per row per step; the
    paged plane slices to the live page horizon, so decode tokens/s
    rises and inter-token p95 stays flat or better, with bit-identical
    greedy tokens (the fallback path is bitwise ``attention_core``)."""
    slot_len = 96 if smoke else 384
    kv_page = kv_page or (16 if smoke else 32)
    workload = make_latency_workload(cfg, max_slots, smoke=smoke, seed=seed)

    def make_engine(paged):
        kw = dict(kv_page=kv_page) if paged else {}
        return ContinuousEngine(params, cfg, max_slots=max_slots,
                                slot_len=slot_len, eos_id=None, **kw)

    # the 2-core CI/dev hosts are noisy: alternate dense/paged passes and
    # keep each engine's best pass, so a background hiccup in one pass
    # cannot flip the comparison
    n_passes = 2 if smoke else 3
    results = {}
    for name, paged in (("dense", False), ("paged", True)):
        _drive_latency(make_engine(paged), workload)   # warmup: compile
        results[name] = {"tok_s": 0.0, "p50_ms": np.inf, "p95_ms": np.inf}
    for _ in range(n_passes):
        for name, paged in (("dense", False), ("paged", True)):
            samples, toks, wall, eng = _drive_latency(make_engine(paged),
                                                      workload)
            n_tok = sum(len(t) for t in toks)
            r = results[name]
            r["tokens"] = toks
            r["peak_kv"] = eng.stats()["kv_peak_positions_reserved"]
            r["tok_s"] = max(r["tok_s"], n_tok / wall)
            r["p50_ms"] = min(r["p50_ms"],
                              float(np.percentile(samples, 50)))
            r["p95_ms"] = min(r["p95_ms"],
                              float(np.percentile(samples, 95)))
    assert results["paged"]["tokens"] == results["dense"]["tokens"], \
        "paged KV changed generated tokens"
    assert results["paged"]["peak_kv"] < results["dense"]["peak_kv"], \
        "paged KV should reserve fewer positions than slot provisioning"
    row = {
        "name": "serve_bench", "scenario": "paged_kv",
        "max_slots": max_slots, "slot_len": slot_len, "kv_page": kv_page,
        "dense_tok_s": round(results["dense"]["tok_s"], 2),
        "paged_tok_s": round(results["paged"]["tok_s"], 2),
        "dense_p50_ms": round(results["dense"]["p50_ms"], 2),
        "paged_p50_ms": round(results["paged"]["p50_ms"], 2),
        "dense_p95_ms": round(results["dense"]["p95_ms"], 2),
        "paged_p95_ms": round(results["paged"]["p95_ms"], 2),
        "tok_s_speedup": round(results["paged"]["tok_s"]
                               / max(1e-9, results["dense"]["tok_s"]), 3),
        "p95_speedup": round(results["dense"]["p95_ms"]
                             / max(1e-9, results["paged"]["p95_ms"]), 3),
        # peak KV positions actually reserved — the deterministic win:
        # dense must hold slot_len per occupied slot, paged holds pages
        # for live context + per-request reservations
        "dense_peak_kv_positions": int(results["dense"]["peak_kv"]),
        "paged_peak_kv_positions": int(results["paged"]["peak_kv"]),
        "kv_memory_ratio": round(results["dense"]["peak_kv"]
                                 / max(1, results["paged"]["peak_kv"]), 2),
        "token_parity": True,
    }
    print(f"[serve_bench] paged KV (slot_len {slot_len}, page {kv_page}): "
          f"dense {row['dense_tok_s']:.1f} tok/s p95 "
          f"{row['dense_p95_ms']:.1f}ms -> paged {row['paged_tok_s']:.1f} "
          f"tok/s p95 {row['paged_p95_ms']:.1f}ms "
          f"({row['tok_s_speedup']:.2f}x tok/s, "
          f"{row['p95_speedup']:.2f}x p95, "
          f"{row['kv_memory_ratio']:.1f}x less peak KV)")
    return row


# ----------------------------------------------------------------------
# workload 4: telemetry overhead (DESIGN.md §10) — the hot-path contract
def run_telemetry_overhead(params, cfg, *, smoke=False, max_slots=4,
                           seed=0):
    """Telemetry OFF vs fully ON (timing + tracing) over the
    same mixed workload.  Asserts the §10 contract: bitwise-identical
    tokens, and with full tracing the decode throughput stays within 5%
    of the uninstrumented engine.  Best-of-N alternating passes (the
    ``run_paged_kv`` de-noising pattern) so a background hiccup on a
    2-core CI host cannot flip the comparison."""
    from repro.obs import Telemetry
    slot_len = 48 if smoke else 128
    workload = make_latency_workload(cfg, max_slots, smoke=smoke, seed=seed)

    def make_engine(on):
        telem = Telemetry(timing=True, trace=True) if on else None
        return ContinuousEngine(params, cfg, max_slots=max_slots,
                                slot_len=slot_len, eos_id=None,
                                telemetry=telem)

    n_passes = 2 if smoke else 3
    results = {}
    for name, on in (("off", False), ("on", True)):
        _drive_latency(make_engine(on), workload)   # warmup: compile
        results[name] = {"tok_s": 0.0}
    for _ in range(n_passes):
        for name, on in (("off", False), ("on", True)):
            samples, toks, wall, eng = _drive_latency(make_engine(on),
                                                      workload)
            n_tok = sum(len(t) for t in toks)
            r = results[name]
            r["tokens"] = toks
            r["tok_s"] = max(r["tok_s"], n_tok / wall)
            if on:
                # record the instrumented pass from the SAME registry the
                # JSON snapshot reads — no side-channel measurements
                snap = eng.metrics()
                r["step_wall_p50_ms"] = snap["step"]["wall_ms"]["p50"]
                r["trace_events"] = len(eng.obs.tracer.events)
    assert results["on"]["tokens"] == results["off"]["tokens"], \
        "telemetry changed generated tokens"
    ratio = results["on"]["tok_s"] / max(1e-9, results["off"]["tok_s"])
    assert ratio >= 0.95, \
        f"telemetry overhead exceeds 5%: {ratio:.3f}x of baseline tok/s"
    row = {
        "name": "serve_bench", "scenario": "telemetry_overhead",
        "max_slots": max_slots, "slot_len": slot_len,
        "off_tok_s": round(results["off"]["tok_s"], 2),
        "on_tok_s": round(results["on"]["tok_s"], 2),
        "overhead_ratio": round(ratio, 4),
        "step_wall_p50_ms": round(results["on"]["step_wall_p50_ms"], 3),
        "trace_events": results["on"]["trace_events"],
        "token_parity": True,
    }
    print(f"[serve_bench] telemetry overhead: off {row['off_tok_s']:.1f} "
          f"tok/s -> on {row['on_tok_s']:.1f} tok/s "
          f"({ratio:.3f}x, tracing enabled; <5% contract holds)")
    return row


# ----------------------------------------------------------------------
# workload 5: prefix reuse (DESIGN.md §13) — shared-system-prompt TTFT
def run_prefix_reuse(params, cfg, *, smoke=False, seed=0):
    """K requests sharing a long system prompt, served one at a time so
    every request after the first can hit the prefix cache.  Measured:
    time-to-first-token of the hit requests vs the same workload with
    the cache disabled — a hit adopts the cached full pages and
    prefills only its tail, so TTFT must drop by at least the 2x the
    ROADMAP asks for (the prefill-work ratio is ~sys_len/tail).  Greedy
    tokens are asserted identical (adopted pages hold bitwise the KV a
    cold prefill would recompute)."""
    page = 8 if smoke else 16
    sys_pages = 6 if smoke else 10
    sys_len = page * sys_pages
    tails = (4, 6, 5) if smoke else (8, 12, 10, 14, 9)
    max_new = 4 if smoke else 8
    rng = np.random.default_rng(seed)
    sys_p = rng.integers(1, cfg.vocab_size, sys_len).astype(np.int32)
    prompts = [np.concatenate(
        [sys_p, rng.integers(1, cfg.vocab_size, t).astype(np.int32)])
        for t in tails]
    slot_len = sys_len + 4 * page
    pages_total = 2 * sys_pages + 8  # pinned chain + one active worst case

    def drive(cache_pages):
        eng = ContinuousEngine(params, cfg, max_slots=1, slot_len=slot_len,
                               eos_id=None, kv_page=page,
                               kv_pages_total=pages_total,
                               prefill_chunk=page,
                               prefix_cache_pages=cache_pages)
        ttfts_ms, toks = [], []
        for p in prompts:
            req = eng.submit(p, max_new)
            t0 = time.perf_counter()
            while not req.generated:
                eng.step()
                assert eng.step_count < 100_000
            ttfts_ms.append((time.perf_counter() - t0) * 1e3)
            eng.run(max_steps=100_000)
            assert req.state == "finished"
            toks.append(req.generated)
        return ttfts_ms, toks, eng

    cache_pages = sys_pages + 2
    drive(0)                      # warmup: compile chunk + decode shapes
    drive(cache_pages)
    n_passes = 2 if smoke else 3
    best = {"cold": np.inf, "hit": np.inf}
    for _ in range(n_passes):
        cold_t, cold_toks, _ = drive(0)
        hit_t, hit_toks, eng = drive(cache_pages)
        assert hit_toks == cold_toks, "prefix cache changed tokens"
        # requests [1:] are the ones whose prefix is published by then
        best["cold"] = min(best["cold"], float(np.median(cold_t[1:])))
        best["hit"] = min(best["hit"], float(np.median(hit_t[1:])))
    assert eng._prefills_skipped == len(prompts) - 1, \
        "every follow-up request should hit the cached system prompt"
    speedup = best["cold"] / max(1e-9, best["hit"])
    floor = 1.5 if smoke else 2.0
    assert speedup >= floor, \
        f"prefix-hit TTFT speedup {speedup:.2f}x below {floor}x"
    row = {
        "name": "serve_bench", "scenario": "prefix_reuse",
        "kv_page": page, "sys_len": sys_len, "n_requests": len(prompts),
        "prefix_cache_pages": cache_pages,
        "cold_ttft_ms": round(best["cold"], 2),
        "hit_ttft_ms": round(best["hit"], 2),
        "ttft_speedup": round(speedup, 3),
        "prefills_skipped": int(eng._prefills_skipped),
        "hit_tokens": int(eng._prefix_hit_tokens),
        "token_parity": True,
    }
    print(f"[serve_bench] prefix reuse (sys {sys_len}, page {page}): "
          f"TTFT cold {row['cold_ttft_ms']:.1f}ms -> hit "
          f"{row['hit_ttft_ms']:.1f}ms ({speedup:.1f}x, "
          f"{row['hit_tokens']} prompt tokens never re-prefilled)")
    return row


# ----------------------------------------------------------------------
# workload 6: overload — optimistic admission + preemption vs stall
def run_overload_preempt(params, cfg, *, smoke=False, seed=0):
    """A pool so starved that every request's worst case IS the whole
    pool: the PR-5 no-preemption discipline reserves the worst case at
    admission and therefore runs requests strictly one at a time.
    Preemption mode admits on prompt-size reservations, grows page by
    page, and swaps a victim to the host pool when the pool runs dry —
    so decode runs batched most of the way and the same backlog drains
    in fewer, fuller steps.  Tokens must stay bitwise (both modes are
    greedy decode; swap round-trips restore KV exactly)."""
    page, max_slots = 4, 4
    n_req = 5 if smoke else 8
    plen = 3
    max_new = 10 if smoke else 28
    pages_total = (plen + max_new + page - 1) // page + 1  # one worst case
    host_pages = 2 * pages_total
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, cfg.vocab_size, plen).astype(np.int32)
               for _ in range(n_req)]

    def drive(preempt):
        kw = (dict(preemption=True, kv_host_pages=host_pages)
              if preempt else {})
        eng = ContinuousEngine(params, cfg, max_slots=max_slots,
                               slot_len=plen + max_new + page, eos_id=None,
                               kv_page=page, kv_pages_total=pages_total,
                               **kw)
        reqs = [eng.submit(p, max_new, priority=i % 2)
                for i, p in enumerate(prompts)]
        t0 = time.perf_counter()
        eng.run(max_steps=100_000)
        wall = time.perf_counter() - t0
        assert all(r.state == "finished" for r in reqs)
        toks = [r.generated for r in reqs]
        return toks, sum(len(t) for t in toks) / wall, eng

    drive(False)                  # warmup: compile both mode's shapes
    drive(True)
    n_passes = 2 if smoke else 3
    best = {"stall": 0.0, "preempt": 0.0}
    for _ in range(n_passes):
        base_toks, tps, _ = drive(False)
        best["stall"] = max(best["stall"], tps)
        toks, tps, eng = drive(True)
        best["preempt"] = max(best["preempt"], tps)
        assert toks == base_toks, "preemption changed generated tokens"
    assert eng.sched.preemptions > 0, \
        "overload workload never triggered a preemption"
    hs = eng.kv.host_stats()
    speedup = best["preempt"] / max(1e-9, best["stall"])
    row = {
        "name": "serve_bench", "scenario": "overload_preempt",
        "kv_page": page, "kv_pages_total": pages_total,
        "kv_host_pages": host_pages, "n_requests": n_req,
        "max_new": max_new,
        "stall_tok_s": round(best["stall"], 2),
        "preempt_tok_s": round(best["preempt"], 2),
        "tok_s_speedup": round(speedup, 3),
        "preemptions": int(eng.sched.preemptions),
        "resumes": int(eng.sched.resumes),
        "recomputes": int(eng._recomputes),
        "swap_out_bytes": int(hs["swap_out_bytes"]),
        "token_parity": True,
    }
    print(f"[serve_bench] overload ({n_req} reqs, {pages_total}-page "
          f"pool): stall {row['stall_tok_s']:.1f} tok/s -> preempt "
          f"{row['preempt_tok_s']:.1f} tok/s ({speedup:.2f}x, "
          f"{row['preemptions']} preemptions, "
          f"{row['swap_out_bytes']} bytes swapped)")
    return row


# ----------------------------------------------------------------------
# workload 8: chaos goodput (DESIGN.md §14) — seeded faults vs fault-free
def run_chaos(params, cfg, off, *, smoke=False, seed=0):
    """Packed continuous serving over paged KV under a seeded fault
    schedule — 5% transient expert-fetch failures, one NaN-poisoned
    decode row, and a couple of client cancellations mid-stream —
    versus the identical fault-free run.  The robustness contract
    (DESIGN.md §14): the engine must not crash, delivered tokens/s must
    stay >= 0.7x the fault-free run, and the page pool must be exactly
    empty at teardown (zero leaked pages, zero dangling refcounts).
    Both runs step with the invariant audit ON, so the wall-clock
    comparison includes the bookkeeping the hardening added."""
    from repro.serving.faults import FaultInjector
    page = 8
    max_slots = 3
    n_req = 8 if smoke else 16
    cancel_stride = 4 if smoke else 6
    rng = np.random.default_rng(seed)
    lengths = (4, 8) if smoke else (8, 16)
    news = (4, 10) if smoke else (8, 24)
    workload = [
        (rng.integers(1, cfg.vocab_size,
                      int(rng.choice(lengths))).astype(np.int32),
         int(rng.integers(*news))) for _ in range(n_req)]
    schedule = "expert_fetch=0.05,nan_logits@3"

    def drive(inject):
        # each drive builds a fresh injector + engine, so every pass of
        # a mode replays the identical deterministic fault schedule
        faults = (FaultInjector.parse(schedule, seed=seed)
                  if inject else None)
        eng = ContinuousEngine(None, cfg, max_slots=max_slots, slot_len=64,
                               eos_id=None, offload=off, kv_page=page,
                               faults=faults, check_invariants=True)
        reqs = [eng.submit(p, m) for p, m in workload]
        victims = ([r for i, r in enumerate(reqs)
                    if i % cancel_stride == cancel_stride - 1]
                   if inject else [])
        t0 = time.perf_counter()
        while eng.sched.has_waiting or eng.sched.n_running or eng._swapped:
            eng.step()
            # a client abandons once it has seen at least one token
            for r in list(victims):
                if r.generated and r.state != "finished":
                    assert eng.cancel(r.rid)
                    victims.remove(r)
            assert eng.step_count < 100_000
        wall = time.perf_counter() - t0
        assert all(r.state == "finished" for r in reqs), "engine hung"
        # goodput = tokens actually delivered to clients (a cancelled
        # request's prefix WAS delivered before the client left)
        delivered = sum(len(r.generated) for r in reqs)
        eng.check_invariants()
        pool = eng.kv.pool
        assert pool.n_free == pool.n_pages and pool.refs == {}, \
            "chaos run leaked pages at teardown"
        return delivered / wall, eng

    drive(False), drive(True)     # warmup: compile both modes' shapes
    n_passes = 2 if smoke else 3
    best = {"clean": 0.0, "chaos": 0.0}
    for _ in range(n_passes):
        tps, _ = drive(False)
        best["clean"] = max(best["clean"], tps)
        tps, eng = drive(True)
        best["chaos"] = max(best["chaos"], tps)
    s = eng.stats()
    assert s["faults_fired_expert_fetch"] > 0, \
        "fault schedule never hit a fetch boundary"
    assert s["faults_failed"] == 1, \
        "NaN poisoning should quarantine exactly one request"
    assert s["faults_cancelled"] == n_req // cancel_stride
    ratio = best["chaos"] / max(1e-9, best["clean"])
    assert ratio >= 0.7, \
        f"chaos goodput {ratio:.2f}x below the 0.7x floor"
    row = {
        "name": "serve_bench", "scenario": "chaos_goodput",
        "n_requests": n_req, "max_slots": max_slots, "kv_page": page,
        "fault_schedule": schedule,
        "clean_tok_s": round(best["clean"], 2),
        "chaos_tok_s": round(best["chaos"], 2),
        "goodput_ratio": round(ratio, 3),
        "fired_expert_fetch": int(s["faults_fired_expert_fetch"]),
        "fetch_retries": int(s["faults_fetch_retries"]),
        "fetch_degraded": int(s["faults_fetch_degraded"]),
        "completed": int(s["faults_completed"]),
        "cancelled": int(s["faults_cancelled"]),
        "failed": int(s["faults_failed"]),
        "pages_leaked": 0,
        "no_crash": True,
    }
    print(f"[serve_bench] chaos ({schedule}): clean "
          f"{row['clean_tok_s']:.1f} tok/s -> chaos "
          f"{row['chaos_tok_s']:.1f} tok/s ({ratio:.2f}x goodput, "
          f"{row['fetch_retries']} retries, {row['fetch_degraded']} "
          f"degraded, {row['cancelled']} cancelled, 0 pages leaked)")
    return row


# ----------------------------------------------------------------------
# workload 7: config-zoo recurrent decode (DESIGN.md §12) — flat cost
def run_zoo(*, smoke=False, seed=0):
    """Recurrent-decode throughput through the SAME ContinuousEngine the
    attention archs use, measured at two context lengths.  The rec
    state plane is O(1) per slot, so per-token decode wall time must
    not grow with how much prompt came before — the measured
    counterpart of the cost model's flat ``recurrent_state_bytes`` term
    (tests/test_paged_kv.py::test_cost_model_recurrent_flat_in_context),
    where an attention arch would pay a KV read that grows linearly."""
    from repro.core.cost_model import (HARDWARE, TokenStats,
                                       tokens_per_second)
    cfg = get_config("xlstm-1.3b").reduced()
    params = T.init_model(jax.random.key(1), cfg)
    rng = np.random.default_rng(seed)
    short_ctx, long_ctx = (8, 64) if smoke else (8, 256)
    max_new = 12 if smoke else 32

    def decode_samples(ctx_len):
        """Median decode-step wall ms at one prompt length (chunked
        prefill at a shared chunk size, so both contexts reuse the same
        compiled shapes and only decode is timed)."""
        eng = ContinuousEngine(params, cfg, max_slots=1,
                               slot_len=ctx_len + max_new + 8, eos_id=None,
                               prefill_chunk=8)
        prompt = rng.integers(1, cfg.vocab_size, ctx_len).astype(np.int32)
        req = eng.submit(prompt, max_new)
        samples = []
        while eng.sched.has_waiting or eng.sched.n_running:
            before = len(req.generated)
            t0 = time.perf_counter()
            eng.step()
            dt_ms = (time.perf_counter() - t0) * 1e3
            if len(req.generated) > before and before > 0:
                samples.append(dt_ms)
            assert eng.step_count < 100_000
        return samples

    # warmup both contexts first (prefill-chunk + decode compilation),
    # then best-of-2 timed passes each so a host hiccup can't fake a slope
    for ctx in (short_ctx, long_ctx):
        decode_samples(ctx)
    ms = {}
    for ctx in (short_ctx, long_ctx):
        ms[ctx] = min(float(np.median(decode_samples(ctx)))
                      for _ in range(2))
    ratio = ms[long_ctx] / max(1e-9, ms[short_ctx])
    assert ratio < 2.0, \
        f"recurrent decode cost grew with context: {ms} ({ratio:.2f}x)"
    stats = TokenStats(0.0, 0.0, 0.0, 0.0)
    hw = HARDWARE["t4"]
    pred = [tokens_per_second(cfg, hw, stats, expert_bits=16, attn_bits=16,
                              context_len=c) for c in (short_ctx, long_ctx)]
    assert pred[0] == pred[1], "cost model: recurrent tok/s must be flat"
    row = {
        "name": "serve_bench", "scenario": "zoo", "arch": cfg.name,
        "short_ctx": short_ctx, "long_ctx": long_ctx, "max_new": max_new,
        "short_ctx_decode_ms": round(ms[short_ctx], 2),
        "long_ctx_decode_ms": round(ms[long_ctx], 2),
        "short_ctx_tok_s": round(1e3 / ms[short_ctx], 2),
        "long_ctx_tok_s": round(1e3 / ms[long_ctx], 2),
        "context_cost_ratio": round(ratio, 3),
        "predicted_tok_s_flat": round(pred[0], 2),
    }
    print(f"[serve_bench] zoo ({cfg.name}): decode "
          f"{row['short_ctx_tok_s']:.1f} tok/s @ctx{short_ctx} vs "
          f"{row['long_ctx_tok_s']:.1f} tok/s @ctx{long_ctx} "
          f"({ratio:.2f}x — flat in context; predicted "
          f"{row['predicted_tok_s_flat']:.2f} tok/s at both)")
    return row


# ----------------------------------------------------------------------
def run(quick=False, trained=False, n_requests=None, max_slots=4,
        slot_len=None, seed=0, overlap=False, prefill_chunk=None):
    cfg = get_config("tiny-moe")
    if trained:
        from benchmarks.common import get_trained_tiny_moe
        params, cfg = get_trained_tiny_moe()
    else:
        params = T.init_model(jax.random.key(0), cfg)

    n = n_requests or (6 if quick else 24)
    slot_len = slot_len or (64 if quick else 128)
    workload = make_workload(cfg, n, seed=seed, smoke=quick)
    # FCFS for the throughput headline: expert-overlap admission pays a
    # per-step routing-collection cost that only pays off when expert
    # loads are expensive (the offloaded regime, priced by the cost
    # model) — pass overlap=True to measure that variant's wall-clock
    policy = ExpertOverlapPolicy(params, cfg) if overlap else None

    static_eng = ServeEngine(params, cfg, SamplerConfig(kind="greedy"))
    cont_eng = ContinuousEngine(params, cfg, max_slots=max_slots,
                                slot_len=slot_len, policy=policy)

    # warmup (compilation) once per engine, then best-of-N ALTERNATING
    # timed passes over the same engines (the run_paged_kv de-noising
    # pattern): a background hiccup on a 2-core host lands in one pass
    # of one engine, not in whichever mode happened to run second
    run_static(static_eng, workload, max_slots)
    run_continuous(cont_eng, workload)
    n_passes = 2 if quick else 3
    t_static = t_cont = np.inf
    for _ in range(n_passes):
        t0 = time.perf_counter()
        static_toks = run_static(static_eng, workload, max_slots)
        t_static = min(t_static, time.perf_counter() - t0)

        t0 = time.perf_counter()
        cont_toks, cont_steps = run_continuous(cont_eng, workload)
        t_cont = min(t_cont, time.perf_counter() - t0)

    # per-request greedy sequences are engine-dependent only through EOS
    # stops (static stops at EOS, and its joint prefill shifts MoE
    # capacity contention), so counts may differ by a few tokens
    drift = abs(cont_toks - static_toks) / max(1, cont_toks)
    assert drift < 0.25, \
        f"token accounting drift too large: {cont_toks} vs {static_toks}"
    tps_static = static_toks / t_static
    tps_cont = cont_toks / t_cont
    speedup = tps_cont / tps_static
    tok_per_step = cont_toks / max(1, cont_steps)
    results = [{
        "name": "serve_bench", "scenario": "continuous_vs_static",
        "n_requests": n, "max_slots": max_slots, "slot_len": slot_len,
        "static_tokens": static_toks, "continuous_tokens": cont_toks,
        "static_s": round(t_static, 3), "static_tok_s": round(tps_static, 2),
        "continuous_s": round(t_cont, 3),
        "continuous_tok_s": round(tps_cont, 2),
        "policy": "overlap" if overlap else "fcfs",
        "speedup": round(speedup, 3),
        "decode_steps": cont_steps, "tokens_per_step": round(
            tok_per_step, 3),
    }]
    print(f"[serve_bench] static  : {tps_static:8.1f} tok/s "
          f"({t_static:.2f}s for {static_toks} tokens)")
    print(f"[serve_bench] contin. : {tps_cont:8.1f} tok/s "
          f"({t_cont:.2f}s, {cont_steps} steps, "
          f"{tok_per_step:.2f} tok/step)")
    print(f"[serve_bench] speedup : {speedup:.2f}x")

    # --- chunked-prefill latency scenarios (plain + packed planes) ----
    chunk = prefill_chunk or (6 if quick else 16)
    lat_slots = 2 if quick else 4
    results.append(run_chunked_latency(params, cfg, chunk=chunk,
                                       smoke=quick, max_slots=lat_slots,
                                       seed=seed))
    from repro.configs.base import OffloadSpec
    from repro.core.offload_engine import OffloadEngine
    # pool == expert count: decode misses are exactly the cold set, so
    # the counter-identity assertion is workload-independent (§8)
    spec = OffloadSpec(cache_size=cfg.moe.num_experts, num_speculative=0,
                       expert_bits=3, attn_bits=4)
    off = OffloadEngine(params, cfg, spec, quantized=True)
    results.append(run_chunked_latency(params, cfg, chunk=chunk,
                                       smoke=quick, max_slots=lat_slots,
                                       offload=off, seed=seed))

    # --- paged KV vs dense ring (DESIGN.md §9) ------------------------
    results.append(run_paged_kv(params, cfg, smoke=quick,
                                max_slots=lat_slots, seed=seed))

    # --- prefix reuse + overload preemption (DESIGN.md §13) -----------
    results.append(run_prefix_reuse(params, cfg, smoke=quick, seed=seed))
    results.append(run_overload_preempt(params, cfg, smoke=quick,
                                        seed=seed))

    # --- telemetry overhead contract (DESIGN.md §10) ------------------
    results.append(run_telemetry_overhead(params, cfg, smoke=quick,
                                          max_slots=lat_slots, seed=seed))

    # --- chaos goodput under seeded faults (DESIGN.md §14) ------------
    results.append(run_chaos(params, cfg, off, smoke=quick, seed=seed))

    # --- config-zoo recurrent decode: flat in context (DESIGN.md §12) -
    results.append(run_zoo(smoke=quick, seed=seed))

    emit(results, "serve_bench")
    (ROOT / "BENCH_serve.json").write_text(json.dumps(results, indent=1))
    print(f"[serve_bench] wrote BENCH_serve.json "
          f"({len(results)} scenario rows)")
    if quick:
        assert speedup > 0.2, "smoke: continuous path unreasonably slow"
        print("[serve_bench] smoke OK")
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny workload for CI (seconds, asserts only)")
    ap.add_argument("--trained", action="store_true",
                    help="use the trained tiny-moe artifact instead of "
                         "random init (slower first run)")
    ap.add_argument("--n-requests", type=int, default=None)
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--slot-len", type=int, default=None)
    ap.add_argument("--prefill-chunk", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--overlap", action="store_true",
                    help="use the expert-overlap admission policy")
    args = ap.parse_args()
    enable_compile_cache()
    run(quick=args.smoke, trained=args.trained, n_requests=args.n_requests,
        max_slots=args.max_slots, slot_len=args.slot_len, seed=args.seed,
        overlap=args.overlap, prefill_chunk=args.prefill_chunk)


if __name__ == "__main__":
    main()
