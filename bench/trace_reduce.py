"""Reduce a profiler trace (``.xplane.pb``) to device busy time, the device
operations that took most time, and the longest idle gaps labelled by
what the benchmark's host loop was doing.

The host loop marks its phases with ``jax.profiler.TraceAnnotation``
spans whose names start with ``bench.`` (``bench.submit``,
``bench.step``, ``bench.host``, ``bench.wait``), which never
nest.  The traced window runs
from the first such span's start to the last one's end.  A device plane
is one named ``/device:TPU:<n>``; its operations are the events of its
``XLA Ops`` line.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict

HOST_PREFIX = "bench."
OPS_LINE = "XLA Ops"


def find_trace(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def _union(intervals):
    """Sorted, merged [(start, end)]."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def load(path):
    """(host spans [(name, start_ns, end_ns)], {device plane: [(op name,
    start_ns, end_ns)]}) from an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host, dev = [], {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((e.name, e.start_ns, e.end_ns)
                               for e in line.events)
            dev[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.end_ns)
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    return host, dev


def op_name(name: str) -> str:
    """An HLO op event's name up to its result shape's layout."""
    return name.split("{", 1)[0].strip()


def reduce(host, dev, top: int = 10, phases=()):
    """The traced window's numbers; ``None`` when the trace holds no host
    span of the benchmark or no device operation.  ``phases``: finer host
    spans (name, start_ns, end_ns) on the trace's clock, siblings too; a
    gap they cover is labelled by them rather than by a ``bench.`` span."""
    if not host or not any(dev.values()):
        return None
    lo = min(s for _, s, _ in host)
    hi = max(e for _, _, e in host)
    window_ns = hi - lo
    busy, per_op = [], defaultdict(float)
    gaps_by = defaultdict(float)
    # the host loop's spans are siblings: at most one covers a time
    levels, cuts = [], []
    for group in (sorted(phases, key=lambda h: h[1]),
                  sorted(host, key=lambda h: h[1])):
        levels.append((group, [s for _, s, _ in group]))
        cuts.extend(t for _, s, e in group for t in (s, e))
    cuts.sort()

    def label_at(t):
        for spans, starts in levels:
            i = bisect.bisect_right(starts, t) - 1
            if i >= 0 and spans[i][1] <= t < spans[i][2]:
                return spans[i][0]
        return "outside"
    for ops in dev.values():
        if not ops:
            continue
        for name, s, e in ops:
            if e > lo and s < hi:
                per_op[op_name(name)] += (min(e, hi) - max(s, lo)) / 1e9
        merged = _union(_clip([(s, e) for _, s, e in ops], lo, hi))
        busy.append(sum(e - s for s, e in merged))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            # split the gap where a host span begins or ends
            pts = cuts[bisect.bisect_right(cuts, a):
                       bisect.bisect_left(cuts, b)]
            for x, y in zip([a] + pts, pts + [b]):
                if y > x:
                    gaps_by[label_at((x + y) / 2)] += (y - x) / 1e9
    n_dev = len([o for o in dev.values() if o])
    busy_s = sum(busy) / n_dev / 1e9
    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / (window_ns / 1e9),
        "device_ops": sorted(([k, v] for k, v in per_op.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v / n_dev] for k, v in gaps_by.items()),
                            key=lambda kv: -kv[1])[:top],
    }


def step_offset_ns(host, step_starts_s):
    """Median offset from ``perf_counter`` seconds to the trace's clock,
    pairing the traced ``bench.step`` spans with the host loop's own
    record of when those steps began (the last ones, in order)."""
    steps = sorted(s for n, s, _ in host if n == "bench.step")
    k = min(len(steps), len(step_starts_s))
    if not k:
        return None
    d = sorted(a - b * 1e9 for a, b in zip(steps[-k:], step_starts_s[-k:]))
    return d[k // 2]
