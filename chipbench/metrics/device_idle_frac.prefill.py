"""Device: 1 - (union of device op intervals / traced slice) in
the prefill cell."""
from chipbench.metrics import idle_frac


def read(view):
    return idle_frac(view)
