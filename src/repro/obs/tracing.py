"""Chrome ``trace_event`` span recording (DESIGN.md §10).

Spans are appended host-side as plain dicts (one append per event, no
formatting until export) and written as the JSON object format
``{"traceEvents": [...]}`` that ``chrome://tracing`` / Perfetto load
directly.  Two process tracks:

* ``pid=0`` **engine** — ``tid=0`` carries the per-step phase spans
  (plan / chunk / dispatch / sync / sample / host nested under each
  ``step <i>`` span by containment; the step span's args count its
  decode rows, prefill chunks, jitted programs and device→host fetches),
  ``tid=1`` one span per executor dispatch site — ``exec.embed``,
  ``exec.mixer``, ``exec.moe``, ``exec.stage``, ``exec.block``,
  ``exec.chunk_moe``, ``exec.head`` (a jitted call with its argument
  preparation), ``exec.state`` (the step's copy of the decode state,
  which the layer programs update in place), ``engine.route_fetch``
  (the step's route-id fetch), ``exec.dispatch`` (the plain plane's one
  program), with ``{"layer": l}`` where a layer
  applies — each inside its step's ``dispatch`` or ``chunk`` phase;
* ``pid=1`` **requests** — one thread per request id, carrying that
  request's lifecycle: ``submit`` instant → ``queue_wait`` span →
  ``prefill[lo:hi)`` span per admission chunk → one ``decode`` span
  (first decode token → finish) → ``finish`` instant.  Gaps between
  prefill chunks are real: they are the steps the budget spent on other
  rows, which is exactly what makes the PR-4 chunked admission and the
  PR-3 overlap pipeline visible on a timeline.

All timestamps come from one ``perf_counter_ns`` origin captured at
construction; ``ts``/``dur`` are microseconds as the format requires.
Spans measure *host-side dispatch-to-return* intervals — device work
dispatched asynchronously shows up in the step's ``sync`` phase (the
point the engine blocks fetching sampled tokens), never as an extra
device synchronization.  The engine track's spans are
``repro.obs.Span`` s, which also annotate a running profile
(``engine.<phase>``, and the ``tid=1`` names above) on the device
trace's clock.
"""
from __future__ import annotations

import json
import time
from typing import Any, Dict, List, Optional

PID_ENGINE = 0
PID_REQUESTS = 1
TID_STEPS = 0
TID_EXEC = 1


class Tracer:
    def __init__(self, clock_ns=time.perf_counter_ns):
        self._clock = clock_ns
        self._t0 = clock_ns()
        self.events: List[Dict[str, Any]] = []
        self._named_threads = set()
        self._named_procs = set()
        self._process_meta(PID_ENGINE, "engine")
        self._process_meta(PID_REQUESTS, "requests")
        self._thread_meta(PID_ENGINE, TID_STEPS, "engine steps")
        self._thread_meta(PID_ENGINE, TID_EXEC, "executor dispatch")

    # ------------------------------------------------------------------
    def now_us(self) -> float:
        return (self._clock() - self._t0) / 1e3

    def _process_meta(self, pid: int, name: str) -> None:
        if pid in self._named_procs:
            return
        self._named_procs.add(pid)
        self.events.append({"ph": "M", "name": "process_name",
                            "pid": pid, "tid": 0, "args": {"name": name}})

    def _thread_meta(self, pid: int, tid: int, name: str) -> None:
        if (pid, tid) in self._named_threads:
            return
        self._named_threads.add((pid, tid))
        self.events.append({"ph": "M", "name": "thread_name", "pid": pid,
                            "tid": tid, "args": {"name": name}})

    def request_track(self, rid: int) -> int:
        """Ensure request ``rid`` has a named thread; returns its tid."""
        self._thread_meta(PID_REQUESTS, rid, f"request {rid}")
        return rid

    # ------------------------------------------------------------------
    def complete(self, name: str, pid: int, tid: int, ts_us: float,
                 dur_us: float, args: Optional[Dict[str, Any]] = None
                 ) -> None:
        ev = {"ph": "X", "name": name, "pid": pid, "tid": tid,
              "ts": ts_us, "dur": max(0.0, dur_us)}
        if args:
            ev["args"] = args
        self.events.append(ev)

    def instant(self, name: str, pid: int, tid: int,
                ts_us: Optional[float] = None,
                args: Optional[Dict[str, Any]] = None) -> None:
        ev = {"ph": "i", "name": name, "pid": pid, "tid": tid,
              "ts": self.now_us() if ts_us is None else ts_us, "s": "t"}
        if args:
            ev["args"] = args
        self.events.append(ev)

    # ------------------------------------------------------------------
    def to_chrome(self) -> Dict[str, Any]:
        return {"traceEvents": self.events, "displayTimeUnit": "ms"}

    def write(self, path) -> None:
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)
