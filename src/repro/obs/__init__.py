"""Unified telemetry plane (DESIGN.md §10): ONE registry feeds the
end-of-run summary, the ``--metrics-json`` snapshot and the Chrome
trace, so no two outputs can ever disagree.

:class:`Telemetry` is the facade engines wire through:

* a :class:`~repro.obs.registry.MetricsRegistry` (always present — even
  ``Telemetry.off()`` serves the pull-time collectors that back the
  legacy ``stats()`` shim);
* an optional :class:`~repro.obs.tracing.Tracer` (``trace=True``)
  recording per-request lifecycle spans, per-step phase spans and the
  executor's dispatch-site spans as Chrome ``trace_event`` JSON;
* :class:`Span` (``timing=True``): one host span written to both sinks
  where the work begins and ends — a Chrome ``X`` event in the tracer
  and a ``jax.profiler.TraceAnnotation``, which lands in a running
  profile's ``.xplane.pb`` on the device trace's clock.

Hot-path contract (tested: ``tests/test_obs.py``, asserted in CI by the
serve_bench ``telemetry_overhead`` scenario): telemetry is host-side
only — it never touches the rng stream, never adds a device
synchronization beyond the counters engines already fetch, generated
tokens are bitwise identical with telemetry on or off, and full tracing
costs <5% decode tokens/s on the mixed serving workload.
"""
from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, List, Optional, Tuple

import jax

from repro.obs.registry import (Counter, Gauge, Histogram, MetricsRegistry,
                                flatten_legacy, metrics_document,
                                write_metrics_json)
from repro.obs.schema import EXEC_KEYS_BY_PLANE, SCHEMA_VERSION
from repro.obs import tracing
from repro.obs.tracing import Tracer

__all__ = ["Telemetry", "MetricsRegistry", "Tracer", "Span", "ExecSpans",
           "Counter", "Gauge", "Histogram", "StepTimer",
           "SpecMetrics", "flatten_legacy", "metrics_document",
           "write_metrics_json", "jit_cache_metrics", "SCHEMA_VERSION"]

_STEP_PHASES = ("plan", "chunk", "dispatch", "sync", "sample", "host")


def jit_cache_metrics() -> Dict[str, int]:
    """``jit`` namespace collector: the process-wide engine-executable
    cache counters (``transformer.cached_jit_stats`` minus the
    unserializable key list)."""
    from repro.models import transformer as T
    s = T.cached_jit_stats()
    return {"builds": s["builds"], "hits": s["hits"],
            "entries": s["entries"]}


class Span:
    """One host span, begun and ended where the work is: on exit it
    writes a Chrome ``X`` event (``name``, pid 0, ``tid``, ``args``) into
    the tracer when tracing, and for its whole extent it holds a
    ``jax.profiler.TraceAnnotation`` named ``label`` (default ``name``),
    which a running profile records on the device trace's clock.
    ``t0``/``t1`` are the telemetry clock's readings at entry and exit.
    Made only by an enabled :class:`Telemetry` (:meth:`Telemetry.span`)."""

    __slots__ = ("_tel", "name", "tid", "args", "label", "t0", "t1", "_ann")

    def __init__(self, tel: "Telemetry", name: str, tid: int,
                 args: Optional[Dict[str, Any]] = None,
                 label: Optional[str] = None):
        self._tel = tel
        self.name = name
        self.tid = tid
        self.args = args
        self.label = name if label is None else label
        self.t0 = self.t1 = 0

    def __enter__(self) -> "Span":
        self._ann = jax.profiler.TraceAnnotation(self.label)
        self._ann.__enter__()
        self.t0 = self._tel.clock_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = self._tel.clock_ns()
        self._ann.__exit__(None, None, None)
        self._ann = None
        tr = self._tel.tracer
        if tr is not None:
            tr.complete(self.name, tracing.PID_ENGINE, self.tid,
                        (self.t0 - tr._t0) / 1e3, (self.t1 - self.t0) / 1e3,
                        args=self.args)


_NO_SPAN = contextlib.nullcontext()  # Telemetry.span with timing off


class ExecSpans:
    """Spans at the executor's dispatch sites (``exec`` namespace, the
    tracer's ``TID_EXEC`` track).  The executor brackets each site with
    :meth:`begin` / :meth:`end`; sites never nest.  A span named
    ``<track>.<site>`` (``exec.mixer``, ``exec.state``,
    ``engine.route_fetch``) adds its host ns to the ``<site>_ns``
    counter; ``end`` also counts the jitted programs the site called
    (``programs``) and the blocking device→host fetches it made
    (``fetches``).  Site names per plane: ``schema.EXEC_KEYS_BY_PLANE``."""

    __slots__ = ("_tel", "_ns", "programs", "fetches", "_open")

    def __init__(self, tel: "Telemetry", plane: str):
        self._tel = tel
        reg = tel.registry
        keys = EXEC_KEYS_BY_PLANE[plane]
        self._ns = {key[:-len("_ns")]: reg.counter("exec", key)
                    for key in keys if key.endswith("_ns")}
        self.programs = reg.counter("exec", "programs")
        self.fetches = (reg.counter("exec", "fetches")
                        if "fetches" in keys else None)
        self._open: Optional[Span] = None

    def begin(self, name: str, layer: Optional[int] = None) -> None:
        sp = Span(self._tel, name, tracing.TID_EXEC,
                  None if layer is None else {"layer": layer})
        sp.__enter__()
        self._open = sp

    def end(self, programs: int = 0, fetches: int = 0) -> None:
        sp = self._open
        self._open = None
        sp.__exit__()
        self._ns[sp.name.split(".", 1)[1]].add(sp.t1 - sp.t0)
        if programs:
            self.programs.add(programs)
        if fetches:
            self.fetches.add(fetches)


class StepTimer:
    """One engine step's phases (``_STEP_PHASES``) as sibling spans on
    the tracer's ``TID_STEPS`` track, each annotated ``engine.<phase>``
    for the profiler: :meth:`phase` ends the open phase and opens the
    next where it starts (re-entering the open phase is a no-op).
    :meth:`Telemetry.step_end` closes the last one and folds the step
    into the ``step`` counters/histogram and its ``step <i>`` span."""

    __slots__ = ("_tel", "index", "phases", "_open", "_exec0")

    def __init__(self, tel: "Telemetry", index: int):
        self._tel = tel
        self.index = index
        self.phases: List[Tuple[str, int, int]] = []  # (phase, t0, t1)
        self._open: Optional[Span] = None
        self._exec0 = tel._exec_tally()

    def phase(self, name: str) -> None:
        sp = self._open
        if sp is not None:
            if sp.name == name:
                return
            self.close()
        sp = Span(self._tel, name, tracing.TID_STEPS,
                  label="engine." + name)
        sp.__enter__()
        self._open = sp

    def close(self) -> None:
        sp = self._open
        if sp is not None:
            self._open = None
            sp.__exit__()
            self.phases.append((sp.name, sp.t0, sp.t1))


class SpecMetrics:
    """``spec`` namespace (DESIGN.md §11): speculation accounting shared
    by both engines.  Declared at wiring time (the full key set exists
    before any round runs — ``schema.SPEC_KEYS``): per-round
    ``proposed``/``accepted`` histograms, a ``rounds`` counter, the
    cumulative ``acceptance_rate`` gauge, and ``bytes_h2d_per_accepted``
    — measured h2d traffic divided by tokens the verify chunks emitted
    (stays 0.0 on engines with resident experts)."""

    __slots__ = ("rounds", "h_proposed", "h_accepted", "g_rate", "g_bytes",
                 "proposed_total", "accepted_total", "emitted_total",
                 "bytes_total")

    def __init__(self, registry: MetricsRegistry):
        self.rounds = registry.counter("spec", "rounds")
        self.h_proposed = registry.histogram("spec", "proposed")
        self.h_accepted = registry.histogram("spec", "accepted")
        self.g_rate = registry.gauge("spec", "acceptance_rate")
        self.g_bytes = registry.gauge("spec", "bytes_h2d_per_accepted")
        self.g_rate.set(0.0)
        self.g_bytes.set(0.0)
        self.proposed_total = 0
        self.accepted_total = 0
        self.emitted_total = 0
        self.bytes_total = 0.0

    def round(self, proposed: int, accepted: int) -> None:
        """One verify round: ``proposed`` = k_eff draft tokens offered,
        ``accepted`` = length of the matching prefix (the round emitted
        ``accepted + 1`` tokens — prefix plus the target's bonus)."""
        self.rounds.add(1)
        self.h_proposed.observe(proposed)
        self.h_accepted.observe(accepted)
        self.proposed_total += int(proposed)
        self.accepted_total += int(accepted)
        self.emitted_total += int(accepted) + 1
        self.g_rate.set(self.accepted_total / max(1, self.proposed_total))

    def add_bytes(self, bytes_h2d: float) -> None:
        """Fold one generation's measured h2d bytes into the
        per-accepted-token gauge."""
        self.bytes_total += float(bytes_h2d)
        self.g_bytes.set(self.bytes_total / max(1, self.emitted_total))


class Telemetry:
    """The facade: ``timing`` enables per-step/per-request measurement
    and the spans' profiler annotations, ``trace`` additionally records
    Chrome trace spans.  ``Telemetry.off()`` keeps only the pull-time
    registry — the zero-cost mode every engine owns by default so
    ``stats()`` always works: no span, clock reading or annotation is
    made (the engine's and executor's ``is not None`` checks on their
    step timer and :class:`ExecSpans` skip every site)."""

    def __init__(self, *, timing: bool = True, trace: bool = False,
                 clock_ns=time.perf_counter_ns):
        self.registry = MetricsRegistry()
        self.timing = timing
        self.clock_ns = clock_ns
        self.tracer: Optional[Tracer] = Tracer(clock_ns) if trace else None
        self._exec: Optional[ExecSpans] = None
        self._step: Dict[str, Any] = {}
        self._req: Dict[str, Any] = {}
        self._req_ts: Dict[int, Dict[str, float]] = {}

    @classmethod
    def off(cls) -> "Telemetry":
        return cls(timing=False, trace=False)

    # ------------------------------------------------------------------
    # schema declaration (engines call at wiring time so snapshots carry
    # the full key set even before any step ran)
    def declare_step_schema(self) -> None:
        r = self.registry
        self._step = {"timed": r.counter("step", "timed"),
                      "wall_ms": r.histogram("step", "wall_ms")}
        for p in _STEP_PHASES:
            self._step[p] = r.counter("step", f"{p}_ns")

    def declare_request_schema(self) -> None:
        r = self.registry
        self._req = {"submitted": r.counter("request", "submitted"),
                     "finished": r.counter("request", "finished"),
                     "queue_wait_steps": r.histogram("request",
                                                     "queue_wait_steps"),
                     "gen_tokens": r.histogram("request", "gen_tokens")}

    def span(self, name: str, track: int = tracing.TID_EXEC, *,
             label: Optional[str] = None, **args):
        """A :class:`Span` on the engine process's ``track`` (a no-op
        context with timing off).  Hot paths do not call this per site
        with timing off: they hold a :class:`StepTimer` /
        :class:`ExecSpans` that is ``None`` then."""
        if not self.timing:
            return _NO_SPAN
        return Span(self, name, track, args or None, label)

    def exec_observer(self, plane: str) -> Optional[ExecSpans]:
        if not self.timing:
            return None
        self._exec = ExecSpans(self, plane)
        return self._exec

    def _exec_tally(self) -> Tuple[int, int]:
        """(programs, fetches) the executor sites have counted so far."""
        xo = self._exec
        if xo is None:
            return 0, 0
        return (xo.programs.value,
                0 if xo.fetches is None else xo.fetches.value)

    # ------------------------------------------------------------------
    # per-step phases
    def step_begin(self, index: int) -> Optional[StepTimer]:
        if not self.timing:
            return None
        return StepTimer(self, index)

    def step_end(self, st: Optional[StepTimer], *, n_decode: int = 0,
                 n_chunks: int = 0) -> None:
        if st is None:
            return
        st.close()
        if not st.phases:
            return
        t0, end = st.phases[0][1], st.phases[-1][2]
        wall_ns = end - t0
        self._step["timed"].add(1)
        self._step["wall_ms"].observe(wall_ns / 1e6)
        for phase, t_lo, t_hi in st.phases:
            self._step[phase].add(t_hi - t_lo)
        if self.tracer is not None:
            programs, fetches = self._exec_tally()
            tr = self.tracer
            tr.complete(f"step {st.index}", tracing.PID_ENGINE,
                        tracing.TID_STEPS, (t0 - tr._t0) / 1e3,
                        wall_ns / 1e3,
                        args={"decode_rows": n_decode, "chunks": n_chunks,
                              "programs": programs - st._exec0[0],
                              "fetches": fetches - st._exec0[1]})

    # ------------------------------------------------------------------
    # request lifecycle
    def req_submitted(self, rid: int, step: int) -> None:
        if not self.timing:
            return
        self._req["submitted"].add(1)
        ts = {"submit": self.clock_ns()}
        self._req_ts[rid] = ts
        if self.tracer is not None:
            tid = self.tracer.request_track(rid)
            self.tracer.instant("submit", tracing.PID_REQUESTS, tid,
                                args={"step": step})

    def req_admitted(self, rid: int, waited_steps: int) -> None:
        if not self.timing:
            return
        self._req["queue_wait_steps"].observe(waited_steps)
        now = self.clock_ns()
        ts = self._req_ts.setdefault(rid, {"submit": now})
        ts["admitted"] = now
        if self.tracer is not None:
            tid = self.tracer.request_track(rid)
            base = self.tracer._t0
            self.tracer.complete(
                "queue_wait", tracing.PID_REQUESTS, tid,
                (ts["submit"] - base) / 1e3, (now - ts["submit"]) / 1e3,
                args={"steps": waited_steps})

    def req_chunk(self, rid: int, lo: int, hi: int, t0_ns: int) -> None:
        if self.tracer is None:
            return
        now = self.clock_ns()
        tid = self.tracer.request_track(rid)
        base = self.tracer._t0
        self.tracer.complete(f"prefill[{lo}:{hi})", tracing.PID_REQUESTS,
                             tid, (t0_ns - base) / 1e3, (now - t0_ns) / 1e3,
                             args={"tokens": hi - lo})

    def req_decode_start(self, rid: int) -> None:
        if not self.timing:
            return
        ts = self._req_ts.get(rid)
        if ts is not None and "decode" not in ts:
            ts["decode"] = self.clock_ns()

    def req_finished(self, rid: int, n_tokens: int, reason: str) -> None:
        if not self.timing:
            return
        self._req["finished"].add(1)
        self._req["gen_tokens"].observe(n_tokens)
        ts = self._req_ts.pop(rid, None)
        if self.tracer is None or ts is None:
            return
        now = self.clock_ns()
        tid = self.tracer.request_track(rid)
        base = self.tracer._t0
        t_dec = ts.get("decode", now)
        self.tracer.complete("decode", tracing.PID_REQUESTS, tid,
                             (t_dec - base) / 1e3, (now - t_dec) / 1e3,
                             args={"tokens": n_tokens, "reason": reason})
        self.tracer.instant("finish", tracing.PID_REQUESTS, tid,
                            args={"tokens": n_tokens, "reason": reason})

    # ------------------------------------------------------------------
    # outputs — all three views read the SAME registry
    def snapshot(self) -> Dict[str, Dict[str, Any]]:
        return self.registry.snapshot()

    def legacy_flat(self) -> Dict[str, Any]:
        return flatten_legacy(self.snapshot())

    def write_metrics(self, path, mode: Optional[Dict[str, Any]] = None
                      ) -> None:
        write_metrics_json(path, self.snapshot(), mode)

    def write_trace(self, path) -> None:
        assert self.tracer is not None, \
            "trace output needs Telemetry(trace=True)"
        self.tracer.write(path)
