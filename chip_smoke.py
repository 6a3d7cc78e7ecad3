"""Bring-up smoke run of offloaded MoE serving on one TPU.

    python chip_smoke.py

Drives the system's main path once, in this one process, at the
published widths and full depth of ``granite-moe-1b-a400m`` (32 experts,
top-8, 24 layers) with random weights made from a seed:

1. ``kernels``   — every Pallas kernel, compiled for the chip, against its
   jnp reference at small shapes;
2. ``build``     — seeded weights, then the packed HQQ expert store
   (``OffloadEngine(quantized=True)``, the ``--offload --quantize`` path);
3. ``serve``     — a few requests of mixed prompt lengths through
   ``ContinuousEngine`` over that store with paged KV and chunked prefill
   (what ``python -m repro.launch.serve --continuous --offload --quantize
   --kv-page 16 --prefill-chunk 16`` runs), then one batch-1
   ``OffloadEngine.generate`` (the paper's interactive mode);
4. ``programs``  — how many ``tpu_custom_call`` (Pallas) ops the compiled
   programs of one packed decode step contain;
5. ``reference`` — prefill and decode logits of the packed plane against
   the dequantized model (``quantize_for_offload`` without packing) run in
   float32 at ``default_matmul_precision("highest")``.

Everything is printed on earlier lines; the last line of standard output
is one JSON object, ``{"ok": true, "device": {...}}``, and only when
every phase passed on a TPU.  Any other backend, or any failed phase,
exits non-zero without that line.  ``tests/test_chip_smoke.py`` runs the
same phases at ``tiny-moe`` size on the CPU.
"""
from __future__ import annotations

import gc
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

MODEL = "granite-moe-1b-a400m"
SEED = 0
KV_PAGE = 16
PREFILL_CHUNK = 16
MAX_SLOTS = 4
SLOT_LEN = 128
# (prompt length, new tokens) per request: five requests for four slots,
# so the last one waits for a slot to free; the lengths split into full
# and partial prefill chunks
REQUESTS = ((16, 24), (23, 32), (48, 20), (37, 28), (64, 24))
B1_REQUEST = (32, 24)          # batch-1 interactive generate
REF_PROMPT, REF_DECODE = 32, 4  # reference comparison: prompt, decode steps

# Logit error is max |packed - reference| over the largest reference
# logit.  The packed plane serves in the config's dtype (bfloat16):
# activations are rounded to an 8-bit mantissa (relative 2^-9) after
# every op and the MXU multiplies in bfloat16, over 24 residual layers,
# and a rounding that moves a token across a top-8 routing tie swaps an
# expert.  The float32 reference rounds nowhere, so a correct plane
# differs from it by a few percent: on the CPU at Granite's widths
# 0.049 with 6 layers and 0.045 with 12, and a bfloat16 run of the
# dequantized model without any offloading differs by as much (0.033,
# 0.044).  A fault in unpacking, slot bookkeeping or layer order moves
# the logits by their own scale (error ~1).  The bound sits between.
LOGIT_TOL = 0.25
# Pallas kernels against their jnp references: the MXU may take float32
# operands in bfloat16 passes, so a correct kernel agrees to ~1e-2 of
# its output scale; an indexing or unpacking fault is O(1).
KERNEL_TOL = 2e-2


def _rel_err(out, ref):
    """(max |out - ref|, that over max |ref|) as floats."""
    import numpy as np
    out = np.asarray(out, np.float64)
    ref = np.asarray(ref, np.float64)
    err = float(np.abs(out - ref).max())
    return err, err / max(float(np.abs(ref).max()), 1e-30)


# ----------------------------------------------------------------------
def check_kernels(log, *, seed: int = SEED, heads=(16, 8, 64)):
    """Run every Pallas kernel (through the dispatching wrappers, on
    shapes that pass their kernel gate) against its jnp reference.
    ``heads`` = (query heads, kv heads, head dim) of the attention cases.
    Returns {kernel: relative error}; raises past ``KERNEL_TOL``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref, resolve_interpret
    from repro.kernels import ragged_attention as RA
    from repro.quant import hqq

    log(f"[kernels] interpret mode: {resolve_interpret()}")
    rng = np.random.default_rng(seed)
    f32 = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    errs = {}
    with jax.default_matmul_precision("highest"):
        B, M, K, N = 3, 8, 256, 256
        for bits in (2, 4):
            w = f32(B, K, N) * 0.05
            qt = hqq.quantize(w, bits)
            x = f32(B, M, K)
            scale, zero = hqq._meta_dequantize(qt)
            want = jnp.stack([ref.dequant_matmul_ref(
                x[b], qt.packed[b], scale[b], zero[b], bits=bits,
                group_size=qt.group_size) for b in range(B)])
            one = hqq.slice_leading(qt, 1)
            errs[f"dequant_matmul_{bits}bit"] = _rel_err(
                ops.dequant_matmul(x[1], one), want[1])[1]
            errs[f"dequant_matmul_batched_{bits}bit"] = _rel_err(
                ops.dequant_matmul_batched(x, qt), want)[1]
            slots = jnp.asarray([2, 0, 2], jnp.int32)
            errs[f"dequant_matmul_slots_{bits}bit"] = _rel_err(
                ops.dequant_matmul_slots(x, qt, slots),
                jnp.stack([ref.dequant_matmul_ref(
                    x[b], qt.packed[s], scale[s], zero[s], bits=bits,
                    group_size=qt.group_size)
                    for b, s in enumerate([2, 0, 2])]))[1]
        H, Hkv, hd = heads
        q, k, v = f32(H, 128, hd), f32(Hkv, 128, hd), f32(Hkv, 128, hd)
        errs["flash_attention"] = _rel_err(
            ops.flash_attention(q, k, v, causal=True),
            ref.flash_attention_ref(q, k, v, causal=True))[1]
        # paged decode: one query per row against a shuffled page pool
        lens, ps, n_pages = [5, 40, 17, 64], KV_PAGE, 16
        tw = -(-max(lens) // ps)
        pages = np.full((len(lens), tw), -1, np.int32)
        ppos = np.full((n_pages, ps), -1, np.int32)
        perm, nxt = rng.permutation(n_pages), 0
        for b, n in enumerate(lens):
            for o in range(-(-n // ps)):
                pid = int(perm[nxt])
                nxt += 1
                pages[b, o] = pid
                live = np.arange(o * ps, (o + 1) * ps)
                ppos[pid] = np.where(live < n, live, -1)
        kp, vp = f32(n_pages, ps, Hkv, hd), f32(n_pages, ps, Hkv, hd)
        qr = f32(len(lens), 1, H, hd)
        qpos = (np.asarray(lens) - 1)[:, None].astype(np.int32)
        wl = RA.build_page_worklist(pages, lens, qpos[:, 0], qpos[:, 0], ps)
        args = (qr, kp, vp, jnp.asarray(ppos), jnp.asarray(pages),
                jnp.asarray(qpos))
        errs["ragged_attention"] = _rel_err(
            RA.ragged_attention(*args, worklist=wl),
            RA.ragged_attention_reference(*args))[1]
    for name, e in errs.items():
        log(f"[kernels] {name}: max rel err {e!r}")
    bad = {n: e for n, e in errs.items() if not e <= KERNEL_TOL}
    if bad:
        raise AssertionError(f"kernels over tolerance {KERNEL_TOL}: {bad}")
    return errs


# ----------------------------------------------------------------------
def build(cfg, seed: int = SEED):
    """Seeded weights + the packed offload engine (``--offload
    --quantize``).  Returns (params, engine)."""
    import jax

    from repro.core.offload_engine import OffloadEngine
    from repro.models import transformer as T

    params = T.init_model(jax.random.key(seed), cfg)
    eng = OffloadEngine(params, cfg, cfg.offload, quantized=True)
    return params, eng


def make_prompts(cfg, lengths, seed: int = SEED):
    import numpy as np
    rng = np.random.default_rng(seed + 1)
    return [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            for n in lengths]


def serve(params, cfg, eng, requests, *, kv_page=KV_PAGE,
          prefill_chunk=PREFILL_CHUNK, max_slots=MAX_SLOTS,
          slot_len=SLOT_LEN, seed: int = SEED):
    """Serve ``requests`` ((prompt len, new tokens), ...) through the
    continuous engine over the packed offload engine, with the engine
    options the ``--continuous --offload --quantize --kv-page
    --prefill-chunk`` CLI passes.  Returns (engine, finished requests)."""
    from repro.serving.engine import ContinuousEngine
    from repro.serving.sampler import SamplerConfig
    from repro.serving.scheduler import ExpertOverlapPolicy

    cont = ContinuousEngine(
        params, cfg, max_slots=max_slots, slot_len=slot_len,
        sampler=SamplerConfig(kind="greedy"),
        policy=ExpertOverlapPolicy(params, cfg),
        prefill_chunk=prefill_chunk, seed=seed, offload=eng,
        kv_page=kv_page)
    prompts = make_prompts(cfg, [n for n, _ in requests], seed)
    reqs = [cont.submit(p, m) for p, (_, m) in zip(prompts, requests)]
    cont.run()
    for r, (n, m) in zip(reqs, requests):
        if r.status != "completed":
            raise AssertionError(f"request {r.rid} ended {r.status!r}")
        toks = list(r.generated)
        if not (1 <= len(toks) <= m) or not all(
                0 <= t < cfg.vocab_size for t in toks):
            raise AssertionError(f"request {r.rid}: bad output {toks}")
        if len(toks) < m and r.finish_reason != "eos":
            raise AssertionError(f"request {r.rid} stopped early "
                                 f"({r.finish_reason}) at {len(toks)}/{m}")
    return cont, reqs


def generate_b1(cfg, eng, request, seed: int = SEED):
    """The paper's interactive mode: batch-1 packed generate."""
    import numpy as np
    n, m = request
    prompt = make_prompts(cfg, [n], seed + 7)[0]
    out, stats = eng.generate(prompt[None], m)
    out = np.asarray(out)
    if out.shape != (1, m) or not ((0 <= out) & (out < cfg.vocab_size)).all():
        raise AssertionError(f"batch-1 generate returned {out!r}")
    return out, stats


def count_custom_calls(eng, cfg, seed: int = SEED):
    """Compile the programs one batch-1 packed decode step dispatches
    (embed, per-layer mixer, MoE, speculative staging, head — every MoE
    layer reuses the same mixer/MoE/stage programs) and count the
    ``tpu_custom_call`` ops in each.  Returns {program: count}."""
    import jax.numpy as jnp

    from repro.models import transformer as T

    ex = eng._exec
    prompt = jnp.asarray(make_prompts(cfg, [8], seed)[0][None])
    _, state, _ = ex.prefill(prompt, 16)
    pstate = ex.init_pool_state()
    tok = prompt[:, -1:]
    lp, kind = ex._layer_p[0], ex.kinds[0]
    st_l = T.decode_state_layer(state, cfg, 0)
    lm = jnp.asarray(0, jnp.int32)
    x = ex._jit_embed(ex.params, tok)
    x2, _, h2 = ex._mixer_blk(kind)(lp, x, st_l, state["pos"], None, None)
    _, _, info = ex._moe_blk()(lp, x2, h2, ex.store, pstate, lm, None)
    pstate = ex.init_pool_state()  # the MoE program donated the old one
    lowered = {
        "embed": ex._jit_embed.lower(ex.params, tok),
        "mixer": ex._mixer_blk(kind).lower(lp, x, st_l, state["pos"],
                                           None, None),
        "moe": ex._moe_blk().lower(lp, x2, h2, ex.store, pstate, lm, None),
        "stage": ex._stage_blk().lower(
            ex.store, pstate, jnp.asarray(1, jnp.int32),
            info["hidden_pre_moe"], ex.routers),
        "head": ex._jit_head.lower(ex.params, x2),
    }
    return {k: lo.compile().as_text().count("tpu_custom_call")
            for k, lo in lowered.items()}


def _real_vocab(cfg, rows):
    """(rows, V) float32 over the real vocabulary: the padded tail of
    the unembedding is masked to -1e30 and is no logit of the model."""
    import numpy as np
    return np.stack(rows).astype(np.float32)[:, :cfg.vocab_size]


def packed_logits(cfg, eng, prompt, forced, chunk=PREFILL_CHUNK):
    """Prefill ``prompt`` on the packed plane (chunked, as served), then
    decode the ``forced`` tokens one at a time.  Returns the last prefill
    position's logits and every decode step's, (1 + len(forced), V)."""
    import jax.numpy as jnp
    import numpy as np

    ex = eng._exec
    max_len = len(prompt) + len(forced) + 1
    logits, state, _ = ex.prefill(jnp.asarray(prompt[None]), max_len,
                                  chunk=chunk)
    rows = [np.asarray(logits[0, -1])]
    pstate = ex.init_pool_state()
    for t in forced:
        logits, state, pstate, _ = ex.decode(
            state, jnp.asarray([[t]], jnp.int32), pstate)
        rows.append(np.asarray(logits[0, -1]))
    return _real_vocab(cfg, rows)


def reference_logits(params, cfg, prompt, forced):
    """The same rows from the dequantized model: ``quantize_for_offload``
    without packing, every leaf in float32, run on the plain plane at
    ``default_matmul_precision("highest")``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.offload_engine import quantize_for_offload
    from repro.runtime import Executor

    cfg32 = cfg.replace(dtype="float32")
    ref, _ = quantize_for_offload(params, cfg32, cfg.offload)
    ref = jax.tree.map(lambda a: a.astype(jnp.float32), ref)
    ex = Executor(ref, cfg32)
    max_len = len(prompt) + len(forced) + 1
    with jax.default_matmul_precision("highest"):
        logits, state, _ = ex.prefill(jnp.asarray(prompt[None]), max_len)
        rows = [np.asarray(logits[0, -1])]
        for t in forced:
            logits, state, _, _ = ex.decode(
                state, jnp.asarray([[t]], jnp.int32))
            rows.append(np.asarray(logits[0, -1]))
    return _real_vocab(cfg, rows)


def reference_inputs(cfg, seed: int = SEED, n_prompt=REF_PROMPT,
                     n_decode=REF_DECODE):
    import numpy as np
    prompt = make_prompts(cfg, [n_prompt], seed + 11)[0]
    forced = np.random.default_rng(seed + 12).integers(
        0, cfg.vocab_size, n_decode).astype(np.int32)
    return prompt, forced


def compare_logits(packed, ref, log, tol=LOGIT_TOL):
    """Max abs / max relative error of ``packed`` against ``ref`` (both
    (rows, V)); relative = abs over the row's largest reference logit.
    Raises past ``tol`` or on a non-finite value."""
    import numpy as np
    if not np.isfinite(packed).all():
        raise AssertionError("packed logits are not finite")
    abs_e, rel_e = 0.0, 0.0
    for i, (p, r) in enumerate(zip(packed, ref)):
        a, rl = _rel_err(p, r)
        what = "prefill" if i == 0 else f"decode {i}"
        log(f"[reference] {what}: max abs err {a!r}, max rel err {rl!r}, "
            f"argmax packed/ref {int(p.argmax())}/{int(r.argmax())}")
        abs_e, rel_e = max(abs_e, a), max(rel_e, rl)
    log(f"[reference] logits vs float32 reference: max abs err {abs_e!r}, "
        f"max rel err {rel_e!r} (tolerance {tol!r})")
    if not rel_e <= tol:
        raise AssertionError(f"logit error {rel_e!r} over tolerance {tol!r}")
    return abs_e, rel_e


# ----------------------------------------------------------------------
def run(cfg, log, *, requests=REQUESTS, b1_request=B1_REQUEST,
        **serve_kw):
    """Every phase, in order, at ``cfg``.  A failed phase is reported and
    the next one still runs where it can.  Returns the report: each
    phase's result under its name, and ``failed``, the failed phases."""
    import jax

    report = {"failed": []}

    def phase(name, fn):
        t0 = time.perf_counter()
        try:
            out = fn()
        except Exception:  # report, then keep the independent phases going
            report["failed"].append(name)
            log(f"[{name}] FAILED after {time.perf_counter() - t0:.1f}s")
            traceback.print_exc()
            return None
        log(f"[{name}] ok in {time.perf_counter() - t0:.1f}s "
            f"(compilation included)")
        report[name] = out
        return out

    heads = (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim)
    phase("kernels", lambda: check_kernels(log, heads=heads))
    built = phase("build", lambda: build(cfg))
    if built is None:
        return report
    params, eng = built
    del report["build"]
    store_arr = eng.store.w_gate.packed
    log(f"[build] {cfg.name}: d_model={cfg.d_model} layers={cfg.n_layers} "
        f"experts={cfg.moe.num_experts} top_k={cfg.moe.top_k} "
        f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} dtype={cfg.dtype}")
    log(f"[build] expert store: {eng.store.nbytes()} bytes at "
        f"{cfg.offload.expert_bits} bits on {store_arr.sharding.device_set}"
        f", memory_kind={store_arr.sharding.memory_kind!r} (device-resident"
        f": moving it to host memory is ROADMAP R1)")

    served = phase("serve", lambda: serve(params, cfg, eng, requests,
                                          **serve_kw))
    if served is not None:
        cont, reqs = served
        s = cont.stats()
        log(f"[serve] {len(reqs)} requests, {s['tokens']} tokens in "
            f"{s['steps']} steps over {cont.max_slots} slots; finish "
            f"reasons {[r.finish_reason for r in reqs]}")
        log(f"[serve] offload counters: hits={s['offload_hits']} "
            f"spec_hits={s['offload_spec_hits']} "
            f"demand_loads={s['offload_demand_loads']} "
            f"spec_loads={s['offload_spec_loads']} counted h2d bytes="
            f"{s['offload_bytes_h2d']!r} (counted, not moved: the store "
            f"is on the device)")
        del cont, reqs, served
        report["serve"] = s
    gen = phase("generate", lambda: generate_b1(cfg, eng, b1_request))
    if gen is not None:
        out, st = gen
        log(f"[generate] batch-1: {out.shape[1]} tokens; hits={st.hits} "
            f"spec_hits={st.spec_hits} demand_loads={st.demand_loads} "
            f"spec_loads={st.spec_loads} counted h2d bytes="
            f"{st.bytes_h2d!r}")
    calls = phase("programs", lambda: count_custom_calls(eng, cfg))
    if calls is not None:
        log(f"[programs] tpu_custom_call per decode-step program: {calls} "
            f"(total {sum(calls.values())})")
    prompt, forced = reference_inputs(cfg)
    got = phase("packed_logits",
                lambda: packed_logits(cfg, eng, prompt, forced))
    # free the packed plane before the float32 reference is built
    del eng, built
    report.pop("generate", None)
    gc.collect()
    want = phase("reference", lambda: reference_logits(params, cfg, prompt,
                                                         forced))
    if got is not None and want is not None:
        phase("compare", lambda: compare_logits(got, want, log))
    stats = jax.devices()[0].memory_stats()
    peak = stats.get("peak_bytes_in_use") if stats else None
    log(f"[memory] peak_bytes_in_use: "
        f"{peak if peak is not None else 'not reported by this backend'}")
    return report


def main() -> int:
    def log(msg):
        print(msg, flush=True)

    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: needs a TPU, JAX's backend is {backend!r}",
              file=sys.stderr)
        return 2
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    log(f"[setup] compile cache: {enable_compile_cache()}")
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    log(f"[setup] device: {device}; jax {jax.__version__}")
    failed = run(get_config(MODEL), log)["failed"]
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
