"""Expert accesses served from the device pool (hits plus prefetch hits)
over all accesses, in percent, over the window (the program's
``offload`` counters)."""


def read(run):
    a, b = run["offload"]["start"], run["offload"]["end"]
    d = {k: b[k] - a[k] for k in ("hits", "spec_hits", "demand_loads")}
    total = sum(d.values())
    return 100.0 * (d["hits"] + d["spec_hits"]) / total if total else None
