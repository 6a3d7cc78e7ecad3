"""FLOPs the model requires for every prompt token prefilled and every
token decoded in the window (``work.py``), over the window's seconds
times the chip's bf16 peak, in percent."""
import record as R


def read(run):
    if not run.get("peaks"):
        return None
    flops, _ = R.window_work(run)
    if not flops:
        return None
    return 100.0 * flops / (run["window_s"] * run["peaks"]["bf16_flops"])
