"""The readers of the worker and fabric layer, and the labelling of device
gaps by the program's spans.

``data/trace_spans.json`` is in the form ``trace.extract`` returns. Its host
events are those of the Python threads in a CPU-profiled prefill task
served through ``executor.submit`` at the ``@smoke`` size (a CPU trace has
no device plane; the CPU backend's compute threads are left out, since on
the chip that work is the device's). Its device ops are placed by hand so
that the device idles inside ``worker.unpack``, ``fabric.put``,
``fabric.dispatch`` and ``endpoint.flush``, and nowhere else."""
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import metrics, peaks, trace, work
from chipbench.harness import ROOT

HERE = Path(__file__).resolve().parent
SPANS = HERE / "data" / "trace_spans.json"
MIX = {"function": "prefill", "prompt_len": 16, "greedy": True,
       "loop": "closed", "concurrency": 64}
READERS = {"worker_host_ms.prefill": "t_w_host",
           "device_wait_ms.prefill": "t_w_device"}


def _view(stamps):
    cfg = json.loads((ROOT / "chipbench/configs/qwen1.5-0.5b.json")
                     .read_text())
    return metrics.View(config=cfg, mix=MIX, stamps=stamps, trace=None,
                        work=work.for_config("qwen1.5-0.5b"),
                        peaks=peaks.for_kind("TPU v5 lite"))


def _split(i):
    t_w = 0.012 + 1e-4 * i
    device = 0.011 - 3e-4 * (i % 4)
    return {"t_s": 1e-4, "t_f": 5e-4, "t_e": 0.02, "t_w": t_w,
            "t_w_host": t_w - device, "t_w_device": device}


@pytest.mark.parametrize("name", sorted(READERS))
def test_split_readers_by_hand(name):
    stamps = [_split(i) for i in range(9)]
    # a task that never reached a worker has no split: left out
    stamps.append({"t_s": 1e-4, "t_f": 5e-4, "t_e": float("nan"),
                   "t_w": float("nan"), "t_w_host": float("nan"),
                   "t_w_device": float("nan")})
    values = sorted(s[READERS[name]] for s in stamps[:9])
    assert metrics.reader(name)(_view(stamps)) == pytest.approx(
        1e3 * values[4])
    # an even count: the mean of the middle two
    values = sorted(s[READERS[name]] for s in stamps[:8])
    assert metrics.reader(name)(_view(stamps[:8])) == pytest.approx(
        1e3 * (values[3] + values[4]) / 2)


@pytest.mark.parametrize("name", sorted(READERS))
def test_split_readers_find_nothing_without_the_split(name):
    # the stamps of a program that does not split t_w: no number, not 0
    old = [{"t_s": 1e-4, "t_f": 5e-4, "t_e": 0.02, "t_w": 0.012}] * 5
    assert metrics.reader(name)(_view(old)) is None
    assert metrics.reader(name)(_view([])) is None


def test_device_gaps_are_labelled_by_the_covering_span():
    ex = trace.load(SPANS)
    host = {n for n, _s, _d, _t in ex["host"]}
    assert {"worker.unpack", "fabric.put", "fabric.dispatch",
            "fabric.fetch", "endpoint.recv", "endpoint.dispatch",
            "endpoint.flush"} <= host
    red = trace.reduce(ex)
    labels = sorted(n for n, _g in red["breakdown"]["idle_gaps"])
    # inside fabric.put an XLA event nests (shard_args) but covers less of
    # the gap; inside fabric.dispatch the jitted call's events cover all
    # of it, as the span does, and the span comes first
    assert labels == ["endpoint.flush", "fabric.dispatch", "fabric.put",
                      "worker.unpack"]
    busy = trace._busy(ex["devices"]["/device:TPU:0"])
    idle = sum(b[0] - a[1] for a, b in zip(busy, busy[1:]))
    assert sum(g for _n, g in red["breakdown"]["idle_gaps"]) == \
        pytest.approx(idle / 1e9)
    assert red["steps"]["prefill_step"]["count"] == 1


def test_each_gap_lies_inside_its_span():
    ex = trace.load(SPANS)
    busy = trace._busy(ex["devices"]["/device:TPU:0"])
    for (_s0, e0), (s1, _e1) in zip(busy, busy[1:]):
        label = trace._label((e0, s1), ex["host"])
        inside = [(s, s + d) for n, s, d, _t in ex["host"]
                  if n == label and s <= e0 and s1 <= s + d]
        assert inside, label
    assert np.all(np.diff([s for s, _e in busy]) > 0)
