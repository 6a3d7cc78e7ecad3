"""Bytes the window's prompt chunks and decode steps require
(``work.py``: weights at their stored precision, the lower bound of
distinct experts, live KV), over the window's seconds times the chip's
HBM bandwidth, in percent."""
import record as R


def read(run):
    if not run.get("peaks"):
        return None
    _, byts = R.window_work(run)
    if not byts:
        return None
    return 100.0 * byts / (run["window_s"]
                           * run["peaks"]["hbm_bytes_per_s"])
