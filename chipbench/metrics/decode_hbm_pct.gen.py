"""Model step: the decode steps' share of their memory roofline, in %:
the bytes one decode step needs (``work.decode`` at the mix's mean
context) times the traced steps, over their device time at the chip's
HBM bandwidth."""
from chipbench.metrics import step, step_work


def read(view):
    st = step(view, "decode_step")
    if not st or st["device_s"] <= 0 or view.peaks is None:
        return None
    nbytes = st["count"] * step_work(view, "decode_step")[1]
    return 100.0 * nbytes / (st["device_s"] * view.peaks["hbm_bytes_per_s"])
