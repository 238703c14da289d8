"""Model step: median of a generation task's ``experts_read`` stamp, the
held experts its decode steps read per expert layer and step (the decode
step's device counter, fetched once a task). A task touches 8 x 6/64 =
0.75 of DeepSeek-V2-Lite's 8 held experts a layer on average; 8 means
every held expert is read. Stamps without it give no number."""
import numpy as np


def read(view):
    vals = [s.get("experts_read", float("nan")) for s in view.stamps]
    vals = [v for v in vals if np.isfinite(v)]
    if not vals:
        return None
    return float(np.median(vals))
