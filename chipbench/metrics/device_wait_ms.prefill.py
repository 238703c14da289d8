"""Worker and fabric: median of the seconds a prefill task's worker
waited in the fabric's blocking device reads (``t_w_device``), in ms.
Stamps without the split of ``t_w`` give no number."""
from chipbench.metrics import stamp_ms


def read(view):
    return stamp_ms(view, lambda s: s.get("t_w_device", float("nan")), 50)
