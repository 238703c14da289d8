"""Model step: device time per jit_decode_step event in the traced
slice, in ms."""
from chipbench.metrics import step


def read(view):
    st = step(view, "decode_step")
    if not st:
        return None
    return 1e3 * st["device_s"] / st["count"]
