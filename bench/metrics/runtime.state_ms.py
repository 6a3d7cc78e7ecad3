"""Median over the window's decode steps of the host time inside the
executor's ``exec.state`` spans (each layer's decode-state slice and
write-back) within the step's ``dispatch`` phase (``dispatch_split.py``)."""
import dispatch_split as D


def read(run):
    return D.median_of(run, "state_ms")
