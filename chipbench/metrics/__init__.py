"""Per-layer metric readers, one file per metric (``<name>.py``), found
by the metric's name in ``BENCHMARK.json``.

A reader is ``read(view) -> float | None``. ``view`` (:class:`View`) holds
what a traced run gathered: the task stamps of the window's finished
tasks, the reduced trace, the configuration, the mix, its work counts and
the chip's peaks. A reader that finds nothing to read returns ``None``,
and the harness leaves that metric out of the result line. The helpers
below hold the arithmetic the readers share.
"""
from __future__ import annotations

import importlib.util
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, List, Optional

import numpy as np

HERE = Path(__file__).resolve().parent


@dataclass
class View:
    config: dict
    mix: dict
    stamps: List[dict]          # Task.latency_breakdown() of each task
    trace: Optional[dict]       # chipbench.trace.reduce(...) of the slice
    work: object                # chipbench/work/<config>.py
    peaks: Optional[dict]       # chipbench/peaks.json entry of the chip


def reader(name: str) -> Callable[[View], Optional[float]]:
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name}", HERE / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def stamp_ms(view: View, part: Callable[[dict], float],
             q: float) -> Optional[float]:
    """Percentile ``q`` of a stamp-derived duration, in ms."""
    vals = [part(s) for s in view.stamps]
    vals = [v for v in vals if np.isfinite(v)]
    if not vals:
        return None
    return float(np.percentile(vals, q)) * 1e3


def step(view: View, name: str) -> Optional[dict]:
    """``{"count", "device_s"}`` of one fabric step in the traced slice."""
    if view.trace is None:
        return None
    return view.trace["steps"].get(name)


def decode_context(mix: dict) -> float:
    """Mean cached positions over the decode steps the mix asks for: a
    request of ``n`` tokens decodes ``n - 1`` steps over ``S + 1`` to
    ``S + n - 1`` positions."""
    S, spec = int(mix["prompt_len"]), mix["output_len"]
    ns = np.arange(spec["min"], spec["max"] + 1)
    steps = (ns - 1).sum()
    return float(sum(S * (n - 1) + n * (n - 1) / 2 for n in ns) / steps)


def step_work(view: View, name: str):
    """``(flops, bytes)`` the algorithm needs for one ``name`` step of
    this cell's traffic."""
    S = int(view.mix["prompt_len"])
    if name == "prefill_step":
        return view.work.prefill(view.config, S)
    return view.work.decode(view.config, decode_context(view.mix))


def mfu_pct(view: View, names) -> Optional[float]:
    """Algorithm FLOPs of the traced steps over their device time at the
    chip's bf16 peak, in %."""
    if view.peaks is None:
        return None
    flops = secs = 0.0
    for name in names:
        st = step(view, name)
        if st is None:
            continue
        flops += st["count"] * step_work(view, name)[0]
        secs += st["device_s"]
    if secs <= 0:
        return None
    return 100.0 * flops / (secs * view.peaks["bf16_flops_per_s"])


def idle_frac(view: View) -> Optional[float]:
    if view.trace is None or view.trace["window_s"] <= 0:
        return None
    return 1.0 - view.trace["busy_s"] / view.trace["window_s"]
