"""Whole step against peak: algorithm FLOPs of the traced prefill and
decode steps over their device time at the bf16 peak, in %."""
from chipbench.metrics import mfu_pct


def read(view):
    return mfu_pct(view, ("prefill_step", "decode_step"))
