"""From a profiler trace to numbers.

:func:`extract` reads the ``.xplane.pb`` that ``jax.profiler`` writes and
keeps what the readers need, as plain lists: per device, the op events and
the jitted-program ("module") events; on the host, every event with its
thread. :func:`reduce` turns that into busy time, idle share, step times
and the breakdown. The extracted form is JSON, so a small recorded trace
can be kept beside the tests.

Step names are matched here and nowhere else (:data:`STEPS`): a rename in
the program shows as a missing step, and its metrics as absent.
"""
from __future__ import annotations

import glob
import json
import os
from typing import Dict, List, Optional, Tuple

# jitted programs of the serving fabric, by the name XLA gives the module
STEPS = {"prefill_step": "jit_prefill_step", "decode_step": "jit_decode_step"}
# host annotations the benchmark's wrappers write (chipbench.remote)
WRAPPERS = ("chipbench.generate", "chipbench.prefill")
_TOP = 10

Event = Tuple[str, int, int]               # name, start ns, duration ns


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _op_name(name: str) -> str:
    """An op event's name is its whole HLO instruction; keep the name
    before the ``=``."""
    return name.split(" = ", 1)[0][:200]


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:") and "CPU" not in plane_name


def extract(path: str) -> dict:
    """``{"devices": {plane: {"ops": [...], "modules": [...]}},
    "host": [[name, start, dur, thread], ...]}`` from one xplane file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, dict] = {}
    host: List[list] = []
    for plane in data.planes:
        if _is_device(plane.name):
            dev = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                kind = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if kind is None:
                    continue
                dev[kind].extend([e.name, int(e.start_ns), int(e.duration_ns)]
                                 for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, int(e.start_ns), int(e.duration_ns),
                             line.name] for e in line.events)
    return {"devices": devices, "host": host}


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _busy(dev: dict) -> List[Tuple[int, int]]:
    events = dev["ops"] or dev["modules"]
    return _union([(s, s + d) for _n, s, d in events])


def step_events(dev: dict, step: str) -> List[Event]:
    """The module events of one fabric step, e.g. ``decode_step``."""
    prefix = STEPS[step]
    return [tuple(e) for e in dev["modules"]
            if e[0] == prefix or e[0].startswith(prefix + "(")
            or e[0].startswith(prefix + ".")]


def _label(gap: Tuple[int, int], host: List[list]) -> str:
    """What the host did in a device gap: the host event that covers most
    of it, preferring anything over the benchmark's own outer spans."""
    s, e = gap
    best, best_cover, wrapper = None, 0, None
    for name, hs, hd, _thread in host:
        cover = min(e, hs + hd) - max(s, hs)
        if cover <= 0:
            continue
        if name in WRAPPERS:
            wrapper = name
            continue
        if cover > best_cover:
            best, best_cover = name, cover
    if best is not None:
        return best[:200]
    return wrapper or "no host event (waiting for work)"


def reduce(extracted: dict, window: Optional[Tuple[int, int]] = None) -> dict:
    """Busy and idle time, step times and the breakdown.

    ``window`` is the traced span in the trace's clock; by default, from
    the first to the last event the trace holds, host or device. Busy time
    is the union of op intervals, averaged over the devices that ran
    anything.
    """
    devices = {k: v for k, v in extracted["devices"].items()
               if v["ops"] or v["modules"]}
    if not devices:
        return {"busy_s": 0.0, "window_s": 0.0, "steps": {},
                "breakdown": {"device_ops": [], "idle_gaps": []}}
    busy_by_dev = {k: _busy(v) for k, v in devices.items()}
    if window is None:
        host = [(s, s + d) for _n, s, d, _t in extracted.get("host", [])]
        ends = [iv[0] for iv in busy_by_dev.values()] + \
            [iv[-1] for iv in busy_by_dev.values()] + host
        window = (min(s for s, _ in ends), max(e for _, e in ends))
    w0, w1 = window
    busy = []
    for ivs in busy_by_dev.values():
        busy.append(sum(max(0, min(e, w1) - max(s, w0)) for s, e in ivs))
    steps = {}
    for step in STEPS:
        evs = [ev for dev in devices.values() for ev in step_events(dev, step)]
        if evs:
            steps[step] = {"count": len(evs),
                           "device_s": sum(d for _n, _s, d in evs) / 1e9}
    op_time: Dict[str, int] = {}
    for dev in devices.values():
        for name, _s, d in dev["ops"]:
            name = _op_name(name)
            op_time[name] = op_time.get(name, 0) + d
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:_TOP]
    first = next(iter(busy_by_dev.values()))
    gaps = [(a[1], b[0]) for a, b in zip(first, first[1:])]
    if first:
        gaps = [(w0, first[0][0])] + gaps + [(first[-1][1], w1)]
    gaps = sorted((g for g in gaps if g[1] > g[0]),
                  key=lambda g: g[0] - g[1])[:_TOP]
    host = extracted.get("host", [])
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "steps": steps,
        "breakdown": {
            "device_ops": [[n, d / 1e9] for n, d in top_ops],
            "idle_gaps": [[_label(g, host), (g[1] - g[0]) / 1e9]
                          for g in gaps],
        },
    }


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)
