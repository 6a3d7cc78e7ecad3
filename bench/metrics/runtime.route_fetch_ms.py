"""Median over the window's decode steps of the host time inside the
engine's ``engine.route_fetch`` span (the blocking fetch of every MoE
layer's route ids) within the step's ``dispatch`` phase
(``dispatch_split.py``)."""
import dispatch_split as D


def read(run):
    return D.median_of(run, "route_fetch_ms")
