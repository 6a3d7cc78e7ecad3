"""Run one benchmark cell once, in this process, on the accelerator.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration file
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``); each per-layer metric is read by
``bench/metrics/<metric>.py`` and the correctness limits of the cell are
in ``bench/limits/<cell>.json``.  Nothing here names a cell, a mix or a
metric: adding one is adding files and entries.

The run builds the program from seeded weights, warms up every shape the
mix uses (set-up) and measures for ``--seconds``.  Then it ends the
requests in flight, serves greedy requests of the mix's sizes through the
same engine and sets them beside the plain reference (``check.py``).
The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace
0``, its per-layer metrics with ``--trace 1``), ``device``, with
``--trace 1`` a ``breakdown``, and last ``check``: each compared number
with its limit.  Without a TPU, or with fewer chips than the cell asks
for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

TRACE_SECONDS = 4.0  # the profiled tail of the window in a --trace 1 run
# the check's greedy requests must finish within this long after the window
CHECK_SECONDS = 200.0
PHASES = ("plan", "chunk", "dispatch", "sync", "sample", "host")


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ----------------------------------------------------------------------
# the cell, found by name
def load_cell(root: Path, name: str):
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; one of {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    mix = json.loads((root / "bench" / "traffic"
                      / f"{cell['traffic']}.json").read_text())
    limits = json.loads((root / "bench" / "limits"
                         / f"{name}.json").read_text())

    def applies(metric):
        return "workloads" not in metric or name in metric["workloads"]

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    per_layer = [m for m in bench["per_layer"] if applies(m)]
    return {"name": name, "cell": cell, "config": config, "mix": mix,
            "limits": limits, "end_to_end": e2e, "per_layer": per_layer,
            "run_seconds": bench["run_seconds"]}


def reader(root: Path, metric_name: str):
    path = root / "bench" / "metrics" / f"{metric_name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric_name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ----------------------------------------------------------------------
def device_info(chips: int):
    """Platform, kind and count of the devices, or :class:`NoChip`."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu":
        raise NoChip(f"no TPU: JAX's devices are {info}")
    if info["count"] < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {info}")
    return info


def peaks_for(kind: str):
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def enable_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed place in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), every program kept."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts program builds (backend compiles and cache loads) while
    armed, through ``jax.monitoring``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event == self.EVENT:
            self.count += 1


# ----------------------------------------------------------------------
def percentile(xs, q):
    import numpy as np
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs \
        else None


def end_to_end(reqs, t0: float, t1: float, open_loop: bool):
    """tokens_s, itl_p95_ms and (open loop) ttft_p95_ms over the window."""
    toks, gaps, ttft = 0, [], []
    for r in reqs:
        tt = r.token_times
        toks += sum(1 for t in tt if t0 <= t <= t1)
        gaps += [(b - a) * 1e3 for a, b in zip(tt, tt[1:])
                 if t0 <= a and b <= t1]
        if open_loop and t0 <= r.due <= t1:
            first = tt[0] if tt and tt[0] <= t1 else t1
            ttft.append((first - r.due) * 1e3)
    out = {"tokens_s": toks / (t1 - t0), "itl_p95_ms": percentile(gaps, 95)}
    if open_loop:
        out["ttft_p95_ms"] = percentile(ttft, 95)
    return out, {"gaps": len(gaps), "ttft_requests": len(ttft)}


def run_cell(root: Path, spec, seed: int, seconds: float, trace: bool,
             *, require_chip: bool = True, controls=(), hooks=None):
    """One run of a cell; returns the result object (the last line).
    ``controls``: activation precisions of the reference (``"fp8"``) to
    put in the program's place as well, on the same requests; each one's
    decision is reported under ``controls``."""
    import jax

    import check as C
    import generator as G
    import serve as S
    import trace_reduce as TR

    cell, config, mix = spec["cell"], spec["config"], spec["mix"]
    if require_chip:
        device = device_info(cell["chips"])
    else:
        d = jax.devices()
        device = {"platform": d[0].platform, "kind": d[0].device_kind,
                  "count": len(d)}
    log(f"[device] platform={device['platform']} kind={device['kind']} "
        f"count={device['count']}")
    if require_chip:
        log(f"[cache] {enable_cache(root)}")
    sys.path.insert(0, str(root / "src"))
    from repro.obs import Telemetry
    telemetry = Telemetry(timing=True, trace=True) if trace else None
    t = S.clock()
    cont, off = S.build(config, mix, seed, telemetry)
    log(f"[setup] weights and packed store built in {S.clock() - t:.3f}s "
        f"({S.clock() - T_PROCESS:.3f}s since start)")
    if hooks:
        hooks(cont, off)
    loop = S.Loop(cont, mix["temperature"])
    chunk = config["serving"]["prefill_chunk"]
    t = S.clock()
    S.warm(loop, mix, chunk, config["model"]["vocab_size"])
    log(f"[setup] warm-up served in {S.clock() - t:.3f}s")
    arr = mix["arrival"]
    open_loop = arr["kind"] == "poisson"
    n = int(arr.get("rate", 0) * (seconds + arr.get("preroll_s", 0)) * 2
            + 64) if open_loop else 4096
    reqs = G.generate(mix, seed, config["model"]["vocab_size"], n)
    counter = CompileCounter()
    off_start = cont.metrics()["offload"]
    jax.effects_barrier()
    preroll = float(arr.get("preroll_s", 0.0))
    t_first = S.clock()
    t0 = t_first + preroll
    setup_s = t0 - T_PROCESS
    t1 = t0 + seconds
    counter.armed = True
    prof_dir = root / "bench" / ".out" / "trace" / spec["name"]
    profiling = False
    if trace:
        import shutil
        shutil.rmtree(prof_dir, ignore_errors=True)
        prof_dir.mkdir(parents=True, exist_ok=True)

    step_starts = []  # perf_counter at each profiled step's start

    def maybe_profile():
        nonlocal profiling
        if trace and not profiling and S.clock() >= t1 - TRACE_SECONDS:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(prof_dir), profiler_options=opts)
            profiling = True

    orig_step = loop.step

    def step():  # the profiler starts between steps, at the traced tail
        maybe_profile()
        if profiling and S.clock() < t1:
            step_starts.append(S.clock())
        return orig_step()
    loop.step = step
    if open_loop:
        S.run_open(loop, reqs, t_first, t1)
    else:
        S.run_closed(loop, reqs, arr.get("clients", 1), t1)
    sent = [r for r in reqs if r.submitted]
    counter.armed = False
    t_close = S.clock()
    loop.step = orig_step
    if profiling:
        jax.profiler.stop_trace()
    log(f"[window] {seconds}s from {preroll}s after arrivals began; "
        f"compiles_in_window={counter.count}; overran by "
        f"{t_close - t1:.4f}s")
    if open_loop:
        late = [r.submitted - r.due for r in sent]
        log(f"[generator] lateness over {len(late)} arrivals: max "
            f"{max(late) if late else 0:.6f}s p95 "
            f"{percentile(late, 95) or 0:.6f}s")
    off_end = cont.metrics()["offload"]
    in_window = [r for r in sent if r.due <= t1]
    attempted = [r for r in in_window if r.due >= t0 or not open_loop]
    failed = [r for r in attempted if r.status not in ("", "completed")]
    e2e, counts = end_to_end(sent, t0, t1, open_loop)
    e2e["setup_s"] = setup_s
    log(f"[window] requests sent {len(sent)}, completed "
        f"{sum(r.status == 'completed' for r in sent)}, failed "
        f"{len(failed)}; inter-token gaps {counts['gaps']}; "
        f"ttft requests {counts['ttft_requests']}")
    record = None
    if trace:
        try:
            host, dev = TR.load(TR.find_trace(str(prof_dir)))
            off_ns = TR.step_offset_ns(host, step_starts)
            base = telemetry.tracer._t0
            phases = [] if off_ns is None else [
                ("engine." + ev["name"],
                 base + ev["ts"] * 1e3 + off_ns,
                 base + (ev["ts"] + ev["dur"]) * 1e3 + off_ns)
                for ev in telemetry.tracer.events
                if ev.get("ph") == "X" and ev.get("pid") == 0
                and ev["name"] in PHASES]
            red = TR.reduce(host, dev, phases=phases)
        except Exception as e:  # the run still reports what it can
            log(f"[trace] not reduced: {type(e).__name__}: {e}")
            red = None
        record = {"window": (t0, t1), "window_s": t1 - t0,
                  "config": config, "mix": mix,
                  "peaks": peaks_for(device["kind"]) if require_chip
                  else None,
                  "steps": list(loop.steps), "requests": sent,
                  "telemetry": list(telemetry.tracer.events),
                  "telemetry_t0_ns": telemetry.tracer._t0,
                  "offload": {"start": off_start, "end": off_end},
                  "trace": red}
    # the check: the window's requests end where they are; greedy
    # requests of the mix's sizes go through the same engine and
    # programs, their logits kept, and are set beside the reference
    loop.cancel_all()
    picked = C.requests(mix, seed, config["model"]["vocab_size"])
    loop.keep_logits()
    t = S.clock()
    for r in picked:
        loop.submit(r)
    loop.drain(t + CHECK_SECONDS)
    log(f"[check] served {len(picked)} greedy requests of "
        f"{sum(r.max_new for r in picked)} tokens in {S.clock() - t:.3f}s")
    stats = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    # the program's state goes before the reference runs
    del loop, cont, off
    gc.collect()
    t = S.clock()
    found = C.compare(root, config, seed, picked, controls)
    log(f"[check] reference ran in {S.clock() - t:.3f}s")
    correct, checks = decide(found["program"], spec["limits"], failed)
    for k, v in found["program"].items():
        log(f"[check] {k}: {v!r}")
    if trace:
        metrics = {}
        for m in spec["per_layer"]:
            v = reader(root, m["name"])(record)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        red = record["trace"]
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
    else:
        metrics = {m["name"]: {"value": e2e.get(m["name"]),
                               "unit": m["unit"]}
                   for m in spec["end_to_end"]}
    out = {"correct": correct, "attempted": len(attempted),
           "failed": len(failed), "metrics": metrics, "device": device}
    if trace and record["trace"] is not None:
        out["breakdown"] = {"device_ops": record["trace"]["device_ops"],
                            "idle_gaps": record["trace"]["idle_gaps"]}
    out["readings"] = found["program"]
    if controls:
        # each control in the program's place, through the same decision
        out["controls"] = {}
        for c in controls:
            ok, cc = decide(found[c], spec["limits"], [])
            out["controls"][c] = {"correct": ok, "check": cc,
                                  "readings": found[c]}
            log(f"[control] {c} in the program's place: correct={ok}; "
                + "; ".join(f"{k} {v['value']!r} limit {v['limit']!r}"
                            for k, v in cc.items()))
    out["check"] = checks
    for name, c in checks.items():
        log(f"check: {name} {c['value']!r} limit {c['limit']!r}")
    return out


def decide(found, limits, failed):
    """(correct, {name: {"value", "limit"}}): every compared number at or
    under its limit, and no request failed."""
    checks = {name: {"value": found.get(name), "limit": lim["limit"]}
              for name, lim in limits.items()}
    correct = not failed and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in checks.values())
    return bool(correct), checks


def main(argv=None, root: Path = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    root = root or BENCH.parent
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    spec = load_cell(root, args.workload)
    try:
        out = run_cell(root, spec, args.seed, args.seconds,
                       bool(args.trace))
    except NoChip as e:
        log(f"run.py: {e}")
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
