"""Worker and fabric: median of a generation task's seconds per token
after the first (``t_w_next``: from the first token to the worker's end,
over the later tokens), in ms. Stamps without it give no number."""
from chipbench.metrics import stamp_ms


def read(view):
    return stamp_ms(view, lambda s: s.get("t_w_next", float("nan")), 50)
