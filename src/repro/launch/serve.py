"""Serving launcher — all three serving modes:

* plain batched serving (fits-in-memory, static batch):
    PYTHONPATH=src python -m repro.launch.serve --arch tiny-moe \
        --prompt "def main(" --max-new 64
* continuous batching with simulated request arrivals (DESIGN.md §4):
    ... --continuous [--n-requests 16] [--arrival-rate 0.5] \
        [--max-slots 4] [--slot-len 256] [--policy overlap]
* the paper's offloaded interactive mode (MoE archs):
    ... --offload [--quantize] [--cache-size 4] [--num-speculative 2]
  (--quantize runs REAL packed execution: HQQ-packed experts streamed
  through the device buffer pool, DESIGN.md §6)
* continuous batching + offloading composed (packed pool shared across
  the running batch):
    ... --continuous --offload --quantize

With ``--offload`` the engine reports cache statistics and the cost-model
tokens/s projection for the paper's four hardware targets.  With
``--continuous`` requests arrive over time (seeded Bernoulli per decode
step), join the running batch as slots free up, and stream per-request.
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import numpy as np

from repro.configs import get_config, list_archs
from repro.core.offload_engine import OffloadEngine
from repro.data.pipeline import decode_bytes, encode_text
from repro.models import transformer as T
from repro.serving.engine import Request, ServeEngine
from repro.serving.sampler import SamplerConfig


def resolve_offload_spec(spec, cache_size=None, num_speculative=None):
    """Overlay CLI offload overrides on an arch's :class:`OffloadSpec`.

    ``None`` means "flag not given"; 0 is a real value — the paper's k=0
    (no cache) and no-speculation ablations must not silently fall back
    to the arch defaults (``args.cache_size or spec.cache_size`` did
    exactly that — regression-tested in ``tests/test_serve_cli.py``).
    """
    if cache_size is None and num_speculative is None:
        return spec
    return dataclasses.replace(
        spec,
        cache_size=spec.cache_size if cache_size is None else cache_size,
        num_speculative=(spec.num_speculative if num_speculative is None
                         else num_speculative))


def resolve_top_k(cfg, top_k_override):
    """MELINOE-style router top-k override: serve an MoE arch with
    fewer experts per token than it was trained with — each dropped
    expert is h2d traffic the offloaded decode never pays.

    ``None`` means "flag not given" (arch default top_k); 0 or negative
    is an explicit error, NOT a fall-through to the default (the same
    or-truthiness trap :func:`resolve_offload_spec` guards — ``k or
    cfg.moe.top_k`` would silently undo an explicit ``0``).  Values
    above the arch's top_k clamp down to it: the router can't route to
    more experts than it scores.
    """
    if top_k_override is None:
        return cfg
    if cfg.moe is None:
        raise ValueError(
            f"--top-k-override targets MoE routing; {cfg.name} is dense")
    k = int(top_k_override)
    if k <= 0:
        raise ValueError(
            f"--top-k-override must be >= 1 (got {k}); every token "
            f"routes to at least one expert")
    k = min(k, cfg.moe.top_k)
    return cfg.replace(moe=dataclasses.replace(cfg.moe, top_k=k))


def resolve_draft(draft_config, num_draft_tokens):
    """CLI speculation flags -> ``(draft_config_name, k)``.

    Speculation is enabled iff a draft config was given AND k resolves
    >= 1; ``--num-draft-tokens`` defaults to 4 when a draft is set but
    the count flag is absent.  ``None`` means "flag not given"; 0 is a
    real value — ``--num-draft-tokens 0`` must disable speculation, not
    fall back to the default k (the same or-truthiness trap
    :func:`resolve_offload_spec` guards; regression-tested in
    ``tests/test_serve_cli.py``).
    """
    if draft_config is None:
        return None, 0
    k = 4 if num_draft_tokens is None else int(num_draft_tokens)
    if k <= 0:
        return None, 0
    return draft_config, k


def resolve_kv_features(prefix_cache, preemption, kv_host_pages):
    """CLI prefix-cache / preemption flags -> ``(prefix_cache_pages,
    preemption_bool, kv_host_pages)`` for the ContinuousEngine.

    ``None`` means "flag not given"; 0 is a real value — ``--prefix-cache
    0`` is the no-cache ablation and ``--kv-host-pages 0`` the
    recompute-only-preemption ablation, and neither may silently fall
    back to a default (the or-truthiness trap
    :func:`resolve_offload_spec` guards; regression-tested in
    ``tests/test_serve_cli.py``).
    """
    pc = 0 if prefix_cache is None else int(prefix_cache)
    if pc < 0:
        raise ValueError(f"--prefix-cache must be >= 0 pages (got {pc}); "
                         f"0 disables the cache")
    pre = preemption == "on"
    if kv_host_pages is not None and not pre:
        raise ValueError(
            "--kv-host-pages sizes the swap pool preemption stages "
            "pages into; add --preemption on (0 with preemption on is "
            "the recompute-only ablation)")
    hp = 0 if kv_host_pages is None else int(kv_host_pages)
    if hp < 0:
        raise ValueError(f"--kv-host-pages must be >= 0 (got {hp}); "
                         f"0 means every preemption recomputes")
    return pc, pre, hp


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", "--config", dest="arch", default="tiny-moe",
                    choices=list_archs(),
                    help="zoo config id (--config is an alias: any "
                         "registry arch serves through the same "
                         "per-layer-kind state planes, DESIGN.md §12)")
    ap.add_argument("--top-k-override", type=int, default=None,
                    metavar="K",
                    help="route each token to min(K, arch top_k) experts "
                         "instead of the arch default — fewer routed "
                         "experts = fewer expert loads over the h2d bus "
                         "in offloaded decode (0/negative is an error, "
                         "not a fall-back to the default)")
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--prompt", action="append", default=None)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--offload", action="store_true")
    ap.add_argument("--quantize", action="store_true")
    ap.add_argument("--cache-size", type=int, default=None)
    ap.add_argument("--num-speculative", type=int, default=None)
    ap.add_argument("--draft-config", default=None, choices=list_archs(),
                    help="token-level draft-and-verify decoding "
                         "(DESIGN.md §11): a dense arch sharing the "
                         "target's vocab proposes tokens the target "
                         "verifies in one chunk — greedy output is "
                         "bitwise identical to non-speculative decode")
    ap.add_argument("--num-draft-tokens", type=int, default=None,
                    help="draft tokens proposed per verify round "
                         "(default 4 when --draft-config is set; 0 "
                         "disables speculation)")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching with simulated arrivals")
    ap.add_argument("--n-requests", type=int, default=12)
    ap.add_argument("--arrival-rate", type=float, default=0.5,
                    help="P(new request arrives) per decode step")
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--slot-len", type=int, default=256)
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked prefill: admit prompts C tokens per "
                         "step so long prompts never stall in-flight "
                         "decodes (bitwise-identical outputs; "
                         "DESIGN.md §8)")
    ap.add_argument("--token-budget", type=int, default=None,
                    help="per-step token cap (decode rows + prefill "
                         "chunks); default max_slots + prefill_chunk")
    ap.add_argument("--kv-page", type=int, default=None,
                    help="block-paged KV (DESIGN.md §9): page size in "
                         "positions; slots allocate pages on demand and "
                         "decode attention is sliced to the live page "
                         "horizon instead of paying slot_len every step")
    ap.add_argument("--kv-pages-total", type=int, default=None,
                    help="shared page-pool size (default: full "
                         "provisioning, max_slots * ceil(slot_len/"
                         "kv_page)); smaller pools gate admission on "
                         "actual KV need instead of slot count")
    ap.add_argument("--prefix-cache", type=int, default=None,
                    metavar="PAGES",
                    help="radix prefix caching (DESIGN.md §13, needs "
                         "--kv-page): keep up to PAGES immutable full "
                         "pages of finished prompts; requests hitting a "
                         "cached prefix adopt those pages and prefill "
                         "only from the divergence point (0 disables — "
                         "a real ablation, not a fall-back)")
    ap.add_argument("--preemption", default="off", choices=["off", "on"],
                    help="preempt-instead-of-refuse admission (DESIGN.md "
                         "§13, needs --kv-page): reserve only the "
                         "prompt's pages, swap the lowest-priority "
                         "victim out when the pool runs dry, resume it "
                         "bitwise later")
    ap.add_argument("--kv-host-pages", type=int, default=None, metavar="N",
                    help="host-side swap pool budget in pages (needs "
                         "--preemption on): preempted KV stages d2h into "
                         "it and back on resume; 0 drops KV and resumes "
                         "by recompute (a real ablation)")
    ap.add_argument("--inject-faults", default=None, metavar="SPEC",
                    help="seeded fault schedule (DESIGN.md §14), e.g. "
                         "'expert_fetch=0.05,nan_logits@2,slow_step@5:25' "
                         "— site=RATE fires per opportunity, site@N,M at "
                         "ordinals, :MS adds a stall; sites: expert_fetch "
                         "swap_out swap_in page_pool nan_logits slow_step "
                         "(seeded by --seed; needs --continuous)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request wall-clock deadline; expired "
                         "requests finish with status deadline_exceeded "
                         "and release every resource they held")
    ap.add_argument("--queue-cap", type=int, default=None,
                    help="bounded admission queue: submissions beyond CAP "
                         "waiting requests are rejected (backpressure) "
                         "instead of growing the queue without bound")
    ap.add_argument("--cancel-every", type=int, default=None, metavar="N",
                    help="cancel every Nth submitted request once it has "
                         "emitted a token — the client-abandonment chaos "
                         "driver (DESIGN.md §14)")
    ap.add_argument("--policy", default="overlap",
                    choices=["fcfs", "overlap"])
    ap.add_argument("--sampler", default="greedy",
                    choices=["greedy", "categorical", "topk"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="write the full namespaced metrics snapshot "
                         "(schema: repro.obs.schema, validated by "
                         "tools/check_metrics_schema.py) and enable "
                         "step/request timing (DESIGN.md §10)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome trace_event JSON (load in "
                         "chrome://tracing or ui.perfetto.dev) of every "
                         "request's lifecycle spans and every step's "
                         "phase breakdown")
    return ap


def make_telemetry(args):
    """CLI flags -> :class:`repro.obs.Telemetry` (off when neither
    output was requested — the engines then carry zero instrumentation)."""
    from repro.obs import Telemetry
    if args.metrics_json is None and args.trace is None:
        return Telemetry.off()
    return Telemetry(timing=True, trace=args.trace is not None)


def write_outputs(args, obs, mode):
    if args.metrics_json is not None:
        obs.write_metrics(args.metrics_json, mode)
        print(f"[obs] metrics -> {args.metrics_json}")
    if args.trace is not None:
        obs.write_trace(args.trace)
        print(f"[obs] trace   -> {args.trace} "
              f"({len(obs.tracer.events)} events; load in chrome://tracing)")


def print_telemetry_summary(obs):
    """End-of-run summary straight from the registry — the same numbers
    the JSON snapshot carries."""
    snap = obs.snapshot()
    step = snap.get("step")
    if step and step["timed"]:
        total = sum(step[f"{p}_ns"] for p in
                    ("plan", "chunk", "dispatch", "sync", "sample", "host"))
        shares = " ".join(
            f"{p}={100 * step[f'{p}_ns'] / max(1, total):.0f}%"
            for p in ("plan", "chunk", "dispatch", "sync", "sample", "host"))
        wall = step["wall_ms"]
        print(f"[obs] step wall p50={wall['p50']:.2f}ms "
              f"p95={wall['p95']:.2f}ms over {step['timed']} timed steps; "
              f"phases: {shares}")


def print_spec_summary(obs):
    snap = obs.snapshot()
    spec = snap.get("spec")
    if spec and spec["rounds"]:
        print(f"[spec] {spec['rounds']} verify rounds, acceptance "
              f"{spec['acceptance_rate']:.2f}, "
              f"{spec['bytes_h2d_per_accepted']/1e6:.2f}MB h2d per "
              f"emitted token")


def main():
    args = build_parser().parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.kv_page is not None and not args.continuous:
        raise SystemExit("--kv-page targets the continuous engine's "
                         "slotted KV plane; add --continuous")
    try:
        prefix_pages, preempt, host_pages = resolve_kv_features(
            args.prefix_cache, args.preemption, args.kv_host_pages)
    except ValueError as e:
        raise SystemExit(str(e))
    if (prefix_pages or preempt) and not args.continuous:
        raise SystemExit("--prefix-cache/--preemption target the "
                         "continuous engine's paged KV plane; add "
                         "--continuous --kv-page")
    if ((args.inject_faults or args.deadline_ms is not None
         or args.queue_cap is not None or args.cancel_every is not None)
            and not args.continuous):
        raise SystemExit("--inject-faults/--deadline-ms/--queue-cap/"
                         "--cancel-every target the continuous engine's "
                         "request lifecycle; add --continuous")
    if ((args.metrics_json is not None or args.trace is not None)
            and not (args.continuous or args.offload)):
        raise SystemExit("--metrics-json/--trace instrument the continuous "
                         "and offload engines; add --continuous or "
                         "--offload")
    draft_name, draft_k = resolve_draft(args.draft_config,
                                        args.num_draft_tokens)
    if draft_name is not None and not (args.offload or args.continuous):
        raise SystemExit("--draft-config targets the offload and "
                         "continuous engines; add --offload or "
                         "--continuous")
    if draft_name is not None and args.sampler != "greedy":
        raise SystemExit("--draft-config needs --sampler greedy (the "
                         "acceptance rule compares argmax streams)")
    telem = make_telemetry(args)
    cfg = get_config(args.arch)
    if cfg.vocab_size > 100_000 or cfg.d_model > 1024:
        cfg = cfg.reduced()
        print(f"[serve] using reduced variant: {cfg.name}")
    try:
        cfg = resolve_top_k(cfg, args.top_k_override)
    except ValueError as e:
        raise SystemExit(str(e))
    if args.top_k_override is not None:
        print(f"[serve] router top-k override: {cfg.moe.top_k} "
              f"experts/token")
    if cfg.is_encoder_decoder and not args.continuous:
        raise SystemExit(
            f"{cfg.name} is encoder-decoder: serve it with --continuous "
            f"(the frontend output is encoded once at admission into the "
            f"shared encoder-KV plane, DESIGN.md §12)")
    rng = jax.random.key(args.seed)
    if args.checkpoint:
        from repro.checkpoint.checkpointer import restore
        tmpl = jax.eval_shape(lambda: T.init_model(rng, cfg))
        params = restore(args.checkpoint, tmpl)
    else:
        params = T.init_model(rng, cfg)
    prompts = args.prompt or ["def main(", "import os\n"]
    enc = [encode_text(p) % cfg.vocab_size for p in prompts]

    offload_eng = None
    if args.offload:
        if cfg.moe is None:
            raise SystemExit("--offload targets MoE archs (the paper's "
                             "technique needs routed experts); dense archs "
                             "use naive streaming — see DESIGN.md §5")
        from repro.configs.base import OffloadSpec
        spec = resolve_offload_spec(cfg.offload or OffloadSpec(),
                                    args.cache_size, args.num_speculative)
        draft = None
        if draft_name is not None and not args.continuous:
            from repro.core.draft import make_draft
            draft = make_draft(draft_name, seed=args.seed)
        eng = OffloadEngine(params, cfg, spec, quantized=args.quantize,
                            telemetry=telem if not args.continuous
                            else None,
                            draft=draft, num_draft_tokens=draft_k)
        if args.continuous:
            # continuous + offloaded decode compose (DESIGN.md §6); the
            # packed pool needs quantized weights
            if not args.quantize:
                raise SystemExit("--continuous --offload needs --quantize "
                                 "(the buffer pool serves HQQ-packed "
                                 "experts)")
            offload_eng = eng
    if args.offload and not args.continuous:
        for p, e in zip(prompts, enc):
            out, stats = eng.generate(e[None], args.max_new)
            print(f"--- prompt {p!r}")
            print("gen:", repr(decode_bytes(out[0])))
            print(f"stats: hit_ratio={stats.hit_ratio:.3f} "
                  f"demand={stats.demand_loads} spec_hits={stats.spec_hits} "
                  f"spec_loads={stats.spec_loads} "
                  f"h2d={stats.bytes_h2d/1e6:.1f}MB")
            for hw in ("t4", "3060", "3080m", "a100"):
                print(f"  {hw:6s}: {eng.throughput_estimate(stats, hw):.2f} "
                      f"tok/s (cost model @ {cfg.name} scale)")
        if eng.size_report:
            print("quantized sizes:", {k: f"{v/1e6:.1f}MB"
                                       for k, v in eng.size_report.items()})
        print_telemetry_summary(eng.obs)
        print_spec_summary(eng.obs)
        write_outputs(args, eng.obs, {
            "engine": "offload", "arch": cfg.name,
            "offloaded": True, "timing": eng.obs.timing,
            "plane": eng._exec.plane,
            "speculative": draft_k > 0})
        return

    if args.continuous:
        from repro.serving.engine import ContinuousEngine
        from repro.serving.scheduler import ExpertOverlapPolicy, fcfs_policy
        policy = (ExpertOverlapPolicy(params, cfg)
                  if args.policy == "overlap" and cfg.moe is not None
                  else fcfs_policy)
        draft_params, draft_cfg = None, None
        if draft_name is not None:
            draft_cfg = get_config(draft_name)
            draft_params = T.init_model(jax.random.key(args.seed),
                                        draft_cfg)
        faults = None
        if args.inject_faults:
            from repro.serving.faults import FaultInjector
            try:
                faults = FaultInjector.parse(args.inject_faults,
                                             seed=args.seed)
            except ValueError as e:
                raise SystemExit(f"--inject-faults: {e}")
        try:
            eng = ContinuousEngine(
                params, cfg, max_slots=args.max_slots,
                slot_len=args.slot_len,
                sampler=SamplerConfig(kind=args.sampler), policy=policy,
                prefill_chunk=args.prefill_chunk,
                token_budget=args.token_budget,
                seed=args.seed, offload=offload_eng,
                kv_page=args.kv_page,
                kv_pages_total=args.kv_pages_total,
                prefix_cache_pages=prefix_pages,
                preemption=preempt,
                kv_host_pages=host_pages,
                telemetry=telem,
                draft_params=draft_params, draft_cfg=draft_cfg,
                num_draft_tokens=draft_k,
                faults=faults, queue_cap=args.queue_cap,
                deadline_ms=args.deadline_ms)
        except ValueError as e:
            raise SystemExit(f"--continuous: {e}")

        def on_finish(req):
            print(f"[step {eng.step_count:4d}] req {req.rid} finished "
                  f"({req.finish_reason}, waited "
                  f"{req.arrival}→{eng.step_count}): "
                  f"{decode_bytes(np.array(req.generated))!r}")

        arrivals = np.random.default_rng(args.seed)
        # enc-dec archs need a frontend output per request; the CLI has
        # no audio pipeline, so a seeded stub stands in for it (the same
        # convention as the smoke tests)
        frontend = np.random.default_rng(args.seed + 1)
        submitted = 0
        rejected = 0
        pending_cancel = []
        # the run must also drain SWAPPED requests: a preempted request
        # is neither waiting nor running while parked off-device
        while submitted < args.n_requests or eng.sched.has_waiting \
                or eng.sched.n_running or eng._swapped:
            idle = (not eng.sched.has_waiting) and eng.sched.n_running == 0 \
                and not eng._swapped
            while (submitted < args.n_requests
                   and (idle or arrivals.random() < args.arrival_rate)):
                idle = False
                e = enc[submitted % len(enc)]
                extras = None
                if cfg.is_encoder_decoder:
                    extras = {"audio_embeds": frontend.standard_normal(
                        (cfg.encoder_seq, cfg.d_model)).astype(np.float32)}
                try:
                    req = eng.submit(e, args.max_new, on_finish=on_finish,
                                     extras=extras)
                except ValueError as err:
                    raise SystemExit(f"--continuous: {err} (raise "
                                     f"--slot-len or lower --max-new)")
                if req.status == "rejected":
                    rejected += 1
                elif (args.cancel_every and submitted % args.cancel_every
                        == args.cancel_every - 1):
                    pending_cancel.append(req)
                submitted += 1
            eng.step()
            # chaos driver: abandon marked requests once they have
            # streamed a token (mid-decode — the interesting case)
            for req in list(pending_cancel):
                if req.state == "finished":
                    pending_cancel.remove(req)
                elif req.generated:
                    eng.cancel(req.rid)
                    pending_cancel.remove(req)
        s = eng.stats()
        print(f"[continuous] {s['finished']} requests, {s['tokens']} tokens "
              f"in {s['steps']} steps ({s['tokens_per_step']:.2f} tok/step, "
              f"{args.max_slots} slots)")
        if args.kv_page is not None:
            print(f"[paged-kv] pool {s['kv_pages_total']} pages x "
                  f"{s['kv_page_size']} positions "
                  f"({s['kv_pages_free']} free at exit); decode attention "
                  f"sliced to the live page horizon (DESIGN.md §9)")
        if offload_eng is not None:
            print(f"[offloaded] pool traffic: {s['offload_demand_loads']} "
                  f"demand + {s['offload_spec_loads']} spec loads, "
                  f"{s['offload_hits']} hits "
                  f"({s['offload_bytes_h2d']/1e6:.1f}MB h2d measured)")
        if prefix_pages:
            pm = eng.metrics()["prefix"]
            print(f"[prefix] {pm['prefills_skipped']} prefills hit the "
                  f"cache ({pm['hit_tokens']} prompt tokens skipped); "
                  f"{pm['nodes']} pages indexed, {pm['evicted_pages']} "
                  f"evicted (DESIGN.md §13)")
        if preempt:
            km = eng.metrics()["kv_host"]
            print(f"[preempt] {km['preemptions']} preemptions, "
                  f"{km['resumes']} resumes ({km['recomputes']} by "
                  f"recompute); swap traffic "
                  f"{(km['swap_out_bytes'] + km['swap_in_bytes'])/1e6:.1f}"
                  f"MB over a {km['pages_total']}-page host pool")
        if (args.inject_faults or args.cancel_every or args.queue_cap
                or args.deadline_ms is not None):
            fm = eng.metrics()["faults"]
            print(f"[faults] {fm['injected']} injected "
                  f"(fetch={fm['fired_expert_fetch']} "
                  f"retries={fm['fetch_retries']} "
                  f"degraded={fm['fetch_degraded']} "
                  f"nan={fm['nan_quarantined']}); terminal statuses: "
                  f"{fm['completed']} completed, {fm['cancelled']} "
                  f"cancelled, {fm['deadline_exceeded']} "
                  f"deadline_exceeded, {fm['rejected']} rejected, "
                  f"{fm['failed']} failed (DESIGN.md §14)")
        print_telemetry_summary(eng.obs)
        print_spec_summary(eng.obs)
        write_outputs(args, eng.obs, {
            "engine": "continuous", "arch": cfg.name,
            "kv_layout": "paged" if args.kv_page is not None else "dense",
            "offloaded": offload_eng is not None,
            "timing": eng.obs.timing, "plane": eng._exec.plane,
            "speculative": draft_k > 0,
            "prefix_cache": prefix_pages > 0, "kv_host": preempt,
            "faults": True})
        return

    eng = ServeEngine(params, cfg, SamplerConfig(kind=args.sampler))
    reqs = [Request(e, args.max_new) for e in enc]
    try:
        served = eng.serve_batch(reqs, seed=args.seed)
    except ValueError as e:
        raise SystemExit(f"serve_batch: {e}")
    for p, r in zip(prompts, served):
        print(f"--- prompt {p!r}\ngen: {decode_bytes(np.array(r.completed))!r}")


if __name__ == "__main__":
    main()
