"""Split each decode step's ``dispatch`` phase by the program's own spans
inside it (``repro.obs``: the executor's dispatch-site spans and the
engine's route-id fetch, on the engine process's second track).

The program's step span (``step <i>``) carries ``decode_rows`` and, from
the program that records the site spans on, ``programs``: the jitted
calls of that step.  A step counts only where its step span has
``programs``; on a program without the site spans every reader of this
module finds nothing and returns ``None``.
"""
from __future__ import annotations

import bisect
import statistics

import record as R

TID_STEPS, TID_EXEC = 0, 1  # repro.obs.tracing's engine tracks
LAUNCH = frozenset({"exec.embed", "exec.mixer", "exec.moe", "exec.stage",
                    "exec.head", "exec.block"})
STATE = frozenset({"exec.state"})
ROUTE_FETCH = frozenset({"engine.route_fetch"})


def _covered(intervals):
    """Length of the union of (start, end) intervals."""
    total, hi = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e > hi:
            total += e - max(s, hi)
            hi = e
    return total


def steps(run):
    """One dict per decode step whose ``dispatch`` span ends in the
    window: ``dispatch_ms``, ``launch_ms``, ``state_ms``,
    ``route_fetch_ms`` (the summed spans of each group inside that
    ``dispatch``), ``self_ms`` (``dispatch`` less the part any span
    inside it covers), ``programs`` and ``decode_rows``."""
    dispatch, step_spans, sites = [], [], []
    for name, _, end, ev in R.spans(run, R.PID_ENGINE):
        lo, hi = ev["ts"], ev["ts"] + ev["dur"]  # microseconds
        if ev["tid"] == TID_EXEC:
            sites.append((lo, hi, name))
        elif name == "dispatch":
            if R.in_window(run, end):
                dispatch.append((lo, hi))
        elif name.startswith("step ") and "programs" in ev.get("args", {}):
            step_spans.append((lo, hi, ev["args"]))
    sites.sort()
    starts = [s for s, _, _ in sites]
    out = []
    for lo, hi in dispatch:
        args = next((a for s, e, a in step_spans if s <= lo and hi <= e),
                    None)
        if args is None:
            continue
        inside = [(s, e, n) for s, e, n in sites[
            bisect.bisect_left(starts, lo):bisect.bisect_right(starts, hi)]
            if e <= hi]

        def ms(names):
            return sum(e - s for s, e, n in inside if n in names) / 1e3
        out.append({
            "dispatch_ms": (hi - lo) / 1e3,
            "launch_ms": ms(LAUNCH), "state_ms": ms(STATE),
            "route_fetch_ms": ms(ROUTE_FETCH),
            "self_ms": (hi - lo - _covered([(s, e) for s, e, _ in inside]))
            / 1e3,
            "programs": args["programs"],
            "decode_rows": args["decode_rows"]})
    return out


def median_of(run, key: str):
    """Median over the window's decode steps of one of ``steps``' times,
    or ``None`` when there is no such step."""
    vals = [s[key] for s in steps(run)]
    return statistics.median(vals) if vals else None
