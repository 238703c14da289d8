"""Service and forwarder: median of t_s + t_f over the window's
prefill tasks, in ms (submit to the endpoint's receipt)."""
from chipbench.metrics import stamp_ms


def read(view):
    return stamp_ms(view, lambda s: s["t_s"] + s["t_f"], 50)
