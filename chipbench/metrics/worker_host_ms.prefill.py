"""Worker and fabric: median of the worker's host work per prefill task
(``t_w_host``: ``t_w`` less the device wait), in ms. Stamps without the
split of ``t_w`` give no number."""
from chipbench.metrics import stamp_ms


def read(view):
    return stamp_ms(view, lambda s: s.get("t_w_host", float("nan")), 50)
