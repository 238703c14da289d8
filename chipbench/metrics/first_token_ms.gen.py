"""Worker and fabric: median of the worker-side time to a generation
task's first token (``t_w_first``: from the worker's start to the moment
the first token's fetch returned), in ms. Stamps without it give no
number."""
from chipbench.metrics import stamp_ms


def read(view):
    return stamp_ms(view, lambda s: s.get("t_w_first", float("nan")), 50)
