"""Median over the window's decode steps of the ``dispatch`` phase's self
time: its duration less the part the program's spans inside it cover
(``dispatch_split.py``).  With ``runtime.launch_ms``,
``runtime.state_ms`` and ``runtime.route_fetch_ms`` it adds up, step by
step, to the step's ``dispatch``."""
import dispatch_split as D


def read(run):
    return D.median_of(run, "self_ms")
