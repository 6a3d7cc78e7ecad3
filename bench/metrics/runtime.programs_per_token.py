"""Jitted programs the window's decode steps called, over the tokens they
decoded: the ``programs`` and ``decode_rows`` of the program's step spans
whose ``dispatch`` phase ended in the window (``dispatch_split.py``)."""
import dispatch_split as D


def read(run):
    steps = D.steps(run)
    rows = sum(s["decode_rows"] for s in steps)
    if not rows:
        return None
    return sum(s["programs"] for s in steps) / rows
