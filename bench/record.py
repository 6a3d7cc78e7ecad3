"""What a ``--trace 1`` run hands the per-layer metric readers, and small
helpers to read it.

``run`` is a dict:

* ``window`` (t0, t1) on ``time.perf_counter`` seconds, ``window_s``;
* ``config``, ``mix``; ``peaks`` (the device's row of ``peaks.json``,
  ``None`` off the chip);
* ``steps``: per engine step, (end time, positions of the tokens it
  decoded);
* ``requests``: the served :class:`generator.Request` s;
* ``telemetry``: the program's Chrome trace events (``repro.obs``) and
  ``telemetry_t0_ns``, their time origin on ``perf_counter_ns``;
* ``offload``: the program's ``offload`` counters at the window's start
  and end;
* ``trace``: ``trace_reduce.reduce`` of the profiled tail (or ``None``).
"""
from __future__ import annotations

import re

PID_ENGINE, PID_REQUESTS = 0, 1  # repro.obs.tracing's process tracks
_CHUNK = re.compile(r"prefill\[(\d+):(\d+)\)")


def in_window(run, t: float) -> bool:
    t0, t1 = run["window"]
    return t0 <= t <= t1


def spans(run, pid: int):
    """(name, start s, end s, event) of the telemetry's complete events
    of one process track, on ``perf_counter`` seconds."""
    base = run["telemetry_t0_ns"] / 1e9
    for ev in run["telemetry"]:
        if ev.get("ph") == "X" and ev.get("pid") == pid:
            s = base + ev["ts"] / 1e6
            yield ev["name"], s, s + ev["dur"] / 1e6, ev


def chunks(run):
    """(request id, lo, hi, end time) of every prompt chunk that ended in
    the window."""
    for name, _, end, ev in spans(run, PID_REQUESTS):
        m = _CHUNK.fullmatch(name)
        if m and in_window(run, end):
            yield ev["tid"], int(m.group(1)), int(m.group(2)), end


def window_work(run):
    """(FLOPs, bytes) the model requires for the prompt chunks and decode
    steps that ended in the window (``work.py``)."""
    import work
    cfg = run["config"]
    prompt_len = {r.rid: len(r.prompt) for r in run["requests"]}
    flops = byts = 0.0
    for t, positions in run["steps"]:
        if positions and in_window(run, t):
            f, b = work.decode_step(cfg, positions)
            flops, byts = flops + f, byts + b
    for rid, lo, hi, _ in chunks(run):
        f, b = work.prefill_chunk(cfg, lo, hi, hi == prompt_len.get(rid))
        flops, byts = flops + f, byts + b
    return flops, byts
