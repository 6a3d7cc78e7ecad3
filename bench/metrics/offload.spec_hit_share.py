"""Expert accesses served by a speculative prefetch, in percent of all
accesses, over the window (the program's ``offload`` counters).  The
program prefetches only at batch 1."""


def read(run):
    a, b = run["offload"]["start"], run["offload"]["end"]
    d = {k: b[k] - a[k] for k in ("hits", "spec_hits", "demand_loads")}
    total = sum(d.values())
    return 100.0 * d["spec_hits"] / total if total else None
