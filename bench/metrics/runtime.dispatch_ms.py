"""Median over the window's steps of the program's ``dispatch`` phase:
the host time spent enqueueing one step's layer programs (the program's
step phase spans)."""
import record as R


def read(run):
    d = sorted((end - start) * 1e3
               for name, start, end, _ in R.spans(run, R.PID_ENGINE)
               if name == "dispatch" and R.in_window(run, end))
    if not d:
        return None
    n = len(d)
    return d[n // 2] if n % 2 else (d[n // 2 - 1] + d[n // 2]) / 2
