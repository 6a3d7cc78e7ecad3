"""Telemetry plane (DESIGN.md §10): registry/histogram/tracer unit
behavior, legacy ``stats()`` projection, snapshot schema exactness for
every engine/plane/KV-layout combination, and the hot-path contract —
tokens are bitwise identical with telemetry fully on (timing + tracing)
or fully off, on every decode plane."""
import importlib.util
import json
from pathlib import Path

import numpy as np
import pytest

from repro.configs.base import OffloadSpec
from repro.core.offload_engine import OffloadEngine
from repro.obs import Telemetry, flatten_legacy
from repro.obs.registry import Histogram, MetricsRegistry
from repro.obs.schema import (EXEC_KEYS_BY_PLANE, HISTOGRAM_FIELDS,
                              JIT_KEYS, OFFLOAD_KEYS, REQUEST_KEYS,
                              SPEC_KEYS, expected_namespaces)
from repro.obs.tracing import PID_REQUESTS, Tracer
from repro.serving.engine import ContinuousEngine
from repro.serving.sampler import SamplerConfig

ROOT = Path(__file__).resolve().parents[1]


def _prompts(cfg, n, seed=0, lo=4, hi=14):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, int(rng.integers(lo, hi)))
            .astype(np.int32) for _ in range(n)]


def _offload_spec():
    return OffloadSpec(cache_size=4, num_speculative=2, expert_bits=3,
                       attn_bits=4)


def _run_serving(cfg, params, telemetry, *, kv_page=None, offload=None,
                 sampler=None, seed=0, **kw):
    eng = ContinuousEngine(params, cfg, max_slots=2, slot_len=48,
                           eos_id=None, kv_page=kv_page, offload=offload,
                           sampler=sampler, seed=seed, telemetry=telemetry,
                           **kw)
    reqs = [eng.submit(p, m) for p, m in
            zip(_prompts(cfg, 4, seed=5), [4, 7, 3, 6])]
    eng.run(max_steps=300)
    assert all(r.state == "finished" for r in reqs)
    return eng, [r.generated for r in reqs]


# ----------------------------------------------------------------------
# registry / histogram / tracer / flatten units
def test_histogram_log_buckets_and_quantiles():
    h = Histogram()
    for v in (1.0, 2.0, 4.0, 4.5, 100.0):
        h.observe(v)
    s = h.snapshot()
    assert s["count"] == 5 and s["sum"] == pytest.approx(111.5)
    assert s["min"] == 1.0 and s["max"] == 100.0
    # each bucket spans one power of two -> estimates within the sample
    # range and monotone across quantiles
    assert s["min"] <= s["p50"] <= s["p95"] <= s["max"]
    # 4.0 and 4.5 land in bucket 3 ([4, 8)); 1.0 in bucket 1; 2.0 in 2
    assert s["buckets"] == {"1": 1, "2": 1, "3": 2, "7": 1}


def test_histogram_empty_snapshot_is_total():
    s = Histogram().snapshot()
    assert set(s) == HISTOGRAM_FIELDS
    assert s["count"] == 0 and s["p95"] == 0.0


def test_registry_kind_conflict_rejected():
    r = MetricsRegistry()
    r.counter("ns", "x")
    r.counter("ns", "x")  # re-declare same kind is idempotent
    with pytest.raises(ValueError):
        r.gauge("ns", "x")


def test_registry_collector_overlap_asserts():
    r = MetricsRegistry()
    r.counter("engine", "steps")
    r.register_collector("engine", lambda: {"steps": 3})
    with pytest.raises(AssertionError):
        r.snapshot()


def test_flatten_legacy_prefixes_and_collisions():
    flat = flatten_legacy({"engine": {"steps": 3}, "kv": {"slots_free": 1},
                           "offload": {"hits": 2}, "step": {"timed": 4}})
    assert flat == {"steps": 3, "kv_slots_free": 1, "offload_hits": 2,
                    "step_timed": 4}
    with pytest.raises(AssertionError):
        flatten_legacy({"kv": {"x": 1}, "engine": {"kv_x": 2}})


def test_tracer_chrome_format_and_metadata_dedup():
    clock = iter(range(0, 10_000_000, 1_000_000))
    tr = Tracer(clock_ns=lambda: next(clock))
    assert tr.request_track(7) == 7
    tr.request_track(7)  # second call must not duplicate the thread meta
    tr.complete("decode", PID_REQUESTS, 7, 10.0, 25.0, args={"tokens": 3})
    tr.instant("finish", PID_REQUESTS, 7)
    doc = tr.to_chrome()
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    names = [(e["ph"], e["name"]) for e in doc["traceEvents"]]
    assert names.count(("M", "thread_name")) == 3  # steps, exec, req 7
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert spans == [{"ph": "X", "name": "decode", "pid": PID_REQUESTS,
                      "tid": 7, "ts": 10.0, "dur": 25.0,
                      "args": {"tokens": 3}}]


# ----------------------------------------------------------------------
# snapshot schema exactness: engines emit EXACTLY the documented key set
def _assert_schema(snapshot, **combo):
    want = expected_namespaces(**combo)
    assert set(snapshot) == set(want), \
        f"namespaces {sorted(snapshot)} != {sorted(want)}"
    for ns in want:
        assert set(snapshot[ns]) == set(want[ns]), \
            f"{ns}: {sorted(set(snapshot[ns]) ^ set(want[ns]))} drifted"


@pytest.mark.parametrize("kv_page", [None, 16], ids=["dense", "paged"])
def test_continuous_snapshot_schema(tiny_moe_cfg, tiny_moe_params, kv_page):
    eng, _ = _run_serving(tiny_moe_cfg, tiny_moe_params,
                          Telemetry(timing=True, trace=True),
                          kv_page=kv_page)
    _assert_schema(eng.metrics(),
                   kv_layout="paged" if kv_page else "dense",
                   timing=True, plane="plain")
    assert set(eng.metrics()["step"]["wall_ms"]) == HISTOGRAM_FIELDS


def test_continuous_snapshot_schema_telemetry_off(tiny_moe_cfg,
                                                  tiny_moe_params):
    eng, _ = _run_serving(tiny_moe_cfg, tiny_moe_params, None)
    _assert_schema(eng.metrics(), kv_layout="dense", timing=False)
    # the legacy flat stats() shim still carries its historical keys
    s = eng.stats()
    for key in ("steps", "tokens", "tokens_per_step", "finished",
                "kv_slots_in_use", "kv_slots_free", "jit_hits"):
        assert key in s, f"legacy stats() lost {key!r}"


def test_offloaded_continuous_snapshot_schema(tiny_moe_cfg,
                                              tiny_moe_params):
    off = OffloadEngine(tiny_moe_params, tiny_moe_cfg, _offload_spec(),
                        quantized=True)
    eng, _ = _run_serving(tiny_moe_cfg, tiny_moe_params,
                          Telemetry(timing=True, trace=True), offload=off)
    snap = eng.metrics()
    _assert_schema(snap, kv_layout="dense", offloaded=True, timing=True,
                   plane="packed_pipelined")
    # the offload namespace carries real traffic
    assert snap["offload"]["demand_loads"] + snap["offload"]["spec_loads"] > 0
    assert "offload_hits" in eng.stats()


def test_speculative_snapshot_schema(tiny_moe_cfg, tiny_moe_params,
                                     tmp_path):
    """Draft-and-verify serving declares the full ``spec`` namespace
    (DESIGN.md §11) — the key set exists even before a round runs, and
    the values account the rounds that did."""
    import jax

    from repro.configs import get_config
    from repro.models import transformer as T
    dcfg = get_config("tiny-draft")
    dparams = T.init_model(jax.random.key(7), dcfg)
    eng, _ = _run_serving(tiny_moe_cfg, tiny_moe_params,
                          Telemetry(timing=True, trace=True),
                          draft_params=dparams, draft_cfg=dcfg,
                          num_draft_tokens=3)
    snap = eng.metrics()
    _assert_schema(snap, kv_layout="dense", timing=True, plane="plain",
                   speculative=True)
    spec = snap["spec"]
    assert set(spec) == SPEC_KEYS
    assert spec["rounds"] > 0
    assert 0.0 <= spec["acceptance_rate"] <= 1.0
    assert set(spec["proposed"]) == HISTOGRAM_FIELDS
    assert spec["proposed"]["count"] == spec["rounds"]
    # acceptance_rate is emitted/proposed accounting: a round emits
    # accepted+1 tokens, so the flat projection carries spec_* keys too
    assert "spec_rounds" in flatten_legacy(snap)
    # the serialized artifact validates against the CI checker
    mpath = tmp_path / "metrics.json"
    eng.obs.write_metrics(mpath, {
        "engine": "continuous", "arch": tiny_moe_cfg.name,
        "kv_layout": "dense", "offloaded": False, "timing": True,
        "plane": "plain", "speculative": True})
    assert _load_checker().check_metrics(mpath) == []


def test_offload_engine_snapshot_schema(tiny_moe_cfg, tiny_moe_params):
    prompt = _prompts(tiny_moe_cfg, 1, seed=2)[0][None]
    off = OffloadEngine(tiny_moe_params, tiny_moe_cfg, _offload_spec(),
                        quantized=True)  # default engine: telemetry off
    off.generate(prompt, 4)
    assert set(off.metrics()) == {"offload", "jit"}
    assert set(off.metrics()["offload"]) == OFFLOAD_KEYS
    telem = Telemetry(timing=True, trace=True)
    on = OffloadEngine(tiny_moe_params, tiny_moe_cfg, _offload_spec(),
                       quantized=True, telemetry=telem)
    on.generate(prompt, 4)
    snap = on.metrics()
    assert set(snap) == {"offload", "jit", "request", "exec"}
    assert set(snap["request"]) == REQUEST_KEYS
    assert set(snap["exec"]) == EXEC_KEYS_BY_PLANE["packed_pipelined"]
    assert set(snap["jit"]) == JIT_KEYS
    assert snap["request"]["finished"] == 1


# ----------------------------------------------------------------------
# hot-path contract: bitwise-identical tokens with telemetry on or off
@pytest.mark.parametrize("mode", ["plain_dense", "plain_paged",
                                  "categorical"])
def test_parity_telemetry_on_off(tiny_moe_cfg, tiny_moe_params, mode):
    kv_page = 16 if mode == "plain_paged" else None
    sampler = (SamplerConfig(kind="categorical")
               if mode == "categorical" else None)
    _, off_toks = _run_serving(tiny_moe_cfg, tiny_moe_params, None,
                               kv_page=kv_page, sampler=sampler, seed=11)
    _, on_toks = _run_serving(tiny_moe_cfg, tiny_moe_params,
                              Telemetry(timing=True, trace=True),
                              kv_page=kv_page, sampler=sampler, seed=11)
    assert on_toks == off_toks, f"{mode}: telemetry perturbed the tokens"


def test_parity_telemetry_on_off_offloaded(tiny_moe_cfg, tiny_moe_params,
                                           monkeypatch):
    names = _record_annotations(monkeypatch)
    off_eng = OffloadEngine(tiny_moe_params, tiny_moe_cfg, _offload_spec(),
                            quantized=True)
    eng, base = _run_serving(tiny_moe_cfg, tiny_moe_params, None,
                             offload=off_eng, seed=11)
    # telemetry off: no span event and no profiler annotation at all
    assert eng.obs.tracer is None and names == []
    _, on = _run_serving(tiny_moe_cfg, tiny_moe_params,
                         Telemetry(timing=True, trace=True),
                         offload=off_eng, seed=11)
    assert on == base, "telemetry perturbed the offloaded packed plane"
    assert names, "telemetry on made no annotation"


# ----------------------------------------------------------------------
# spans at the dispatch sites (exec track) and the step phases as spans
_PHASES = ("plan", "chunk", "dispatch", "sync", "sample", "host")


def _record_annotations(monkeypatch):
    """Replace ``jax.profiler.TraceAnnotation`` with a recorder; returns
    the list the names it is made with are appended to."""
    import jax
    names = []

    class Recorder:
        def __init__(self, name, **kw):
            names.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    return names


def _serve_b1_offloaded(cfg, params, telemetry):
    """Batch-1 offloaded serving on the packed pipelined plane (speculative
    staging on), paged KV and chunked prefill: two requests of 4 decode
    steps each."""
    off = OffloadEngine(params, cfg, _offload_spec(), quantized=True)
    eng = ContinuousEngine(params, cfg, max_slots=1, slot_len=40,
                           eos_id=None, kv_page=8, prefill_chunk=8,
                           offload=off, telemetry=telemetry)
    for p in _prompts(cfg, 2, seed=4, lo=9, hi=13):
        eng.submit(p, 5)
    eng.run(max_steps=100)
    return eng, off


def _spans(tel, tid):
    return [(e["ts"], e["ts"] + e["dur"], e["name"], e.get("args", {}))
            for e in tel.tracer.events
            if e["ph"] == "X" and e["pid"] == 0 and e["tid"] == tid]


def test_exec_spans_nest_in_their_step_phase(tiny_moe_cfg, tiny_moe_params):
    tel = Telemetry(timing=True, trace=True)
    eng, off = _serve_b1_offloaded(tiny_moe_cfg, tiny_moe_params, tel)
    steps = _spans(tel, 0)
    sites = _spans(tel, 1)
    assert sites
    # the step track holds the step spans and the six phases only; no
    # site span is named as a phase
    assert {n for _, _, n, _ in steps if not n.startswith("step ")} \
        <= set(_PHASES)
    assert all(n.startswith(("exec.", "engine.")) and n not in _PHASES
               for _, _, n, _ in sites)
    phases = [(lo, hi, n) for lo, hi, n, _ in steps
              if n in ("dispatch", "chunk")]
    for lo, hi, name, args in sites:
        parent = [n for plo, phi, n in phases if plo <= lo and hi <= phi]
        assert len(parent) == 1, (name, lo, hi)
        if name in ("engine.route_fetch", "exec.moe", "exec.stage"):
            assert parent == ["dispatch"]
        if name == "exec.chunk_moe":
            assert parent == ["chunk"]
        assert ("layer" in args) == (name in (
            "exec.mixer", "exec.moe", "exec.stage", "exec.chunk_moe"))
    # sites never overlap: their sum is the part of dispatch they cover
    ordered = sorted(sites)
    assert all(a[1] <= b[0] for a, b in zip(ordered, ordered[1:]))


def test_step_span_counts_programs_and_fetches(tiny_moe_cfg,
                                               tiny_moe_params):
    tel = Telemetry(timing=True, trace=True)
    eng, off = _serve_b1_offloaded(tiny_moe_cfg, tiny_moe_params, tel)
    n_moe = off.n_moe_layers
    sites = _spans(tel, 1)
    decode_steps = 0
    total = {"programs": 0, "fetches": 0}
    for lo, hi, name, args in _spans(tel, 0):
        if not name.startswith("step "):
            continue
        total["programs"] += args["programs"]
        total["fetches"] += args["fetches"]
        if args["decode_rows"]:
            decode_steps += 1
            stages = sum(1 for s, e, n, _ in sites
                         if n == "exec.stage" and lo <= s and e <= hi)
            assert stages == n_moe - 1  # lookahead 1
            # one copy of the decode state a step, not a slice and a
            # write-back a layer: the layer programs update it in place
            assert sum(1 for s, e, n, _ in sites
                       if n == "exec.state" and lo <= s and e <= hi) == 1
            assert args["programs"] == 2 + 2 * n_moe + stages
            assert args["fetches"] == n_moe  # one route-id array a layer
    assert decode_steps == 2 * 4
    snap = tel.snapshot()["exec"]
    assert snap["programs"] == total["programs"]
    assert snap["fetches"] == total["fetches"] == decode_steps * n_moe
    assert set(snap) == EXEC_KEYS_BY_PLANE["packed_pipelined"]
    for key in ("embed_ns", "mixer_ns", "moe_ns", "stage_ns", "head_ns",
                "state_ns", "chunk_moe_ns", "route_fetch_ns"):
        assert snap[key] > 0, key
    # each counter is the sum of its spans (us in the trace, ns here)
    mixer_us = sum(e - s for s, e, n, _ in sites if n == "exec.mixer")
    assert snap["mixer_ns"] / 1e3 == pytest.approx(mixer_us, rel=1e-6)


def test_span_annotations_follow_timing(tiny_moe_cfg, tiny_moe_params,
                                        monkeypatch):
    names = _record_annotations(monkeypatch)
    # telemetry off: spans are no-ops, nothing is annotated
    with Telemetry.off().span("exec.embed"):
        pass
    assert names == []
    # timing without tracing still annotates, and writes no event
    tel = Telemetry(timing=True, trace=False)
    _serve_b1_offloaded(tiny_moe_cfg, tiny_moe_params, tel)
    assert tel.tracer is None
    got = set(names)
    assert {"engine." + p for p in ("plan", "chunk", "dispatch", "sync",
                                    "host")} <= got
    assert {"exec.embed", "exec.mixer", "exec.moe", "exec.stage",
            "exec.head", "exec.state", "exec.chunk_moe",
            "engine.route_fetch"} <= got
    assert all(n.startswith(("engine.", "exec.")) for n in got)
    assert not any(n.startswith("bench.") for n in got)
    # one span, both sinks: the Chrome event and the annotation
    names.clear()
    tel = Telemetry(timing=True, trace=True)
    with tel.span("exec.mixer", 1, layer=3):
        pass
    assert names == ["exec.mixer"]
    ev = tel.tracer.events[-1]
    assert (ev["name"], ev["pid"], ev["tid"], ev["args"]) == (
        "exec.mixer", 0, 1, {"layer": 3})


def test_parity_offload_engine_generate(tiny_moe_cfg, tiny_moe_params):
    prompt = _prompts(tiny_moe_cfg, 1, seed=9)[0][None]
    base = OffloadEngine(tiny_moe_params, tiny_moe_cfg, _offload_spec(),
                         quantized=True)
    out0, stats0 = base.generate(prompt, 6)
    on = OffloadEngine(tiny_moe_params, tiny_moe_cfg, _offload_spec(),
                       quantized=True,
                       telemetry=Telemetry(timing=True, trace=True))
    out1, stats1 = on.generate(prompt, 6)
    assert (out0 == out1).all()
    assert (stats0.hits, stats0.demand_loads) == \
        (stats1.hits, stats1.demand_loads)


# ----------------------------------------------------------------------
# serialized artifacts validate against the CI checker itself
def _load_checker():
    spec = importlib.util.spec_from_file_location(
        "check_metrics_schema", ROOT / "tools" / "check_metrics_schema.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_metrics_and_trace_files_pass_ci_checker(tiny_moe_cfg,
                                                 tiny_moe_params, tmp_path):
    eng, _ = _run_serving(tiny_moe_cfg, tiny_moe_params,
                          Telemetry(timing=True, trace=True))
    mpath, tpath = tmp_path / "metrics.json", tmp_path / "trace.json"
    eng.obs.write_metrics(mpath, {
        "engine": "continuous", "arch": tiny_moe_cfg.name,
        "kv_layout": "dense", "offloaded": False, "timing": True,
        "plane": "plain"})
    eng.obs.write_trace(tpath)
    checker = _load_checker()
    assert checker.check_metrics(mpath) == []
    assert checker.check_trace(tpath) == []
    # and the checker actually rejects drift
    doc = json.loads(mpath.read_text())
    del doc["metrics"]["engine"]["steps"]
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(doc))
    assert checker.check_metrics(bad), "checker passed a broken snapshot"
