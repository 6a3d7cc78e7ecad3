"""Share of the profiled tail of the window in which no operation ran on
the device, in percent (``trace_reduce.py``)."""


def read(run):
    t = run.get("trace")
    if not t or not t["window_s"]:
        return None
    return 100.0 * t["idle_share"]
