"""Median over the window's decode steps of the host time inside the
executor's program-call spans (``exec.embed``, ``exec.mixer``,
``exec.moe``, ``exec.stage``, ``exec.head``, ``exec.block``: argument
preparation and the jitted call) within the step's ``dispatch`` phase
(``dispatch_split.py``)."""
import dispatch_split as D


def read(run):
    return D.median_of(run, "launch_ms")
