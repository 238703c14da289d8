"""The yardstick's pure parts: traffic, work counts, peaks, the trace
reduction and the metric readers, and that every cell resolves its files."""
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import check, metrics, peaks, trace, traffic, work
from chipbench.harness import ROOT, find_cell
from chipbench.tests import smoke

HERE = Path(__file__).resolve().parent
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
MIXES = {p.stem: traffic.load_mix(p.stem)
         for p in (ROOT / "chipbench" / "traffic").glob("*.json")}
# the open-loop generator, which no cell in BENCHMARK.json uses yet
MIXES["smoke-gen-open"] = smoke.GEN
PREFILL_MIX = "prefill16-closed64"


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_traffic_is_a_function_of_the_seed(mix):
    m = MIXES[mix]
    a = traffic.schedule(m, 2 ** 33 + 5, 10, 1000)
    b = traffic.schedule(m, 2 ** 33 + 5, 10, 1000)
    c = traffic.schedule(m, 2 ** 33 + 6, 10, 1000)
    assert [(r.due_s, r.n_tokens) for r in a] == \
        [(r.due_s, r.n_tokens) for r in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))
    assert all(r.prompt.shape == (1, m["prompt_len"]) for r in a)
    assert all(1 <= r.prompt.min() and r.prompt.max() < 1000 for r in a)
    # another seed: the same schedule and sizes, other tokens
    assert [(r.due_s, r.n_tokens) for r in a] == \
        [(r.due_s, r.n_tokens) for r in c]
    assert not all((x.prompt == y.prompt).all() for x, y in zip(a, c))
    if m["loop"] == "open":
        assert 0 < a[0].due_s and a[-1].due_s < 10
        rate = len(traffic.schedule(m, 1, 100, 1000)) / 100
        assert rate == pytest.approx(m["rate_per_s"], rel=0.15)
        # the schedule of a shorter window is the start of a longer one's
        short = traffic.schedule(m, 2 ** 33 + 5, 5, 1000)
        assert [r.due_s for r in short] == [r.due_s for r in a][:len(short)]
        lo, hi = m["output_len"]["min"], m["output_len"]["max"]
        assert all(lo <= r.n_tokens <= hi for r in a)


def test_qwen_decode_work_matches_hand_count():
    cfg = json.loads((ROOT / "chipbench/configs/qwen1.5-0.5b.json").read_text())
    w = work.for_config("qwen1.5-0.5b")
    # 24 layers of (4 * 1024^2 + 3 * 1024 * 2816) plus a 1024 x 151,936 head
    weights = 24 * (4 * 1024 ** 2 + 3 * 1024 * 2816) + 1024 * 151_936
    flops, nbytes = w.decode(cfg, 1)
    assert flops == pytest.approx(2 * weights, rel=1e-3)      # ~0.93 GFLOP
    assert nbytes == pytest.approx(2 * weights, rel=1e-3)     # ~0.93 GB
    assert 0.92e9 < flops < 0.94e9 and 0.92e9 < nbytes < 0.94e9
    # each cached position adds K and V reads, 2 * 24 * 1024 * 2 bytes
    assert w.decode(cfg, 101)[1] - nbytes == 100 * 2 * 24 * 1024 * 2
    # prefill of S tokens: 2 S x the layer weights, causal attention, one head
    f16, _ = w.prefill(cfg, 16)
    layer = 24 * (4 * 1024 ** 2 + 3 * 1024 * 2816)
    assert f16 == 2 * 16 * layer + 4 * 24 * 1024 * 136 + 2 * 1024 * 151_936


def test_peaks_refuse_an_unknown_device_kind():
    assert peaks.for_kind("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.for_kind("TPU v99")


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves_its_files(name):
    cell, config_entry = find_cell(BENCH, name)
    config = json.loads((ROOT / config_entry["file"]).read_text())
    assert config["endpoint"]["workers"] == 2
    assert traffic.load_mix(cell["traffic"])["function"] in (
        "generate", "prefill")
    limits = json.loads(
        (ROOT / "chipbench/checks" / f"{name}.json").read_text())
    assert limits["max_logit_gap"]["limit"] > 0
    w = work.for_config(config_entry["name"])
    assert w.prefill(config, 16)[0] > 0 and w.decode(config, 16)[0] > 0
    for m in BENCH["per_layer"]:
        if name in m.get("workloads", [name]):
            assert callable(metrics.reader(m["name"]))


# -- the trace reduction on a small trace -----------------------------------
# ``data/trace_small.json`` is in the form ``trace.extract`` returns, laid
# out like a traced slice of the gen cell on the chip: one prefill, then
# three decode steps (a scan, then the head) with host round trips between.

SMALL = HERE / "data" / "trace_small.json"


def test_reduction_of_the_small_trace():
    ex = trace.load(SMALL)
    red = trace.reduce(ex)
    dev = next(iter(ex["devices"].values()))
    ops = [(s, s + d) for _n, s, d in dev["ops"]]
    merged = trace._union(ops)
    assert red["busy_s"] == pytest.approx(
        sum(e - s for s, e in merged) / 1e9)
    assert 0 < red["busy_s"] < red["window_s"]
    for step in ("prefill_step", "decode_step"):
        evs = trace.step_events(dev, step)
        assert red["steps"][step]["count"] == len(evs) > 0
        assert red["steps"][step]["device_s"] == pytest.approx(
            sum(d for _n, _s, d in evs) / 1e9)
    bd = red["breakdown"]
    assert 0 < len(bd["device_ops"]) <= 10 and 0 < len(bd["idle_gaps"]) <= 10
    assert bd["device_ops"] == sorted(bd["device_ops"], key=lambda x: -x[1])
    assert sum(g for _n, g in bd["idle_gaps"]) <= red["window_s"] - red["busy_s"] + 1e-9


def test_union_and_idle_by_hand():
    ex = {"devices": {"/device:TPU:0": {
        "ops": [["a", 0, 10], ["b", 5, 10], ["c", 30, 10]],
        "modules": [["jit_decode_step(7)", 0, 15], ["jit_prefill_step(3)", 30, 10]]}},
        "host": [["wait", 15, 15, "t"], ["t0", 0, 1, "t"], ["t1", 99, 1, "t"]]}
    red = trace.reduce(ex)
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["busy_s"] == pytest.approx(25e-9)
    assert red["steps"]["decode_step"] == {"count": 1, "device_s": 15e-9}
    assert red["breakdown"]["idle_gaps"][0] == ["t1", pytest.approx(60e-9)]
    assert ["wait", pytest.approx(15e-9)] in red["breakdown"]["idle_gaps"]


def _view(cell_mix, cfg_name, stamps, reduced):
    cfg = json.loads((ROOT / f"chipbench/configs/{cfg_name}.json").read_text())
    return metrics.View(config=cfg, mix=MIXES[cell_mix],
                        stamps=stamps, trace=reduced,
                        work=work.for_config(cfg_name),
                        peaks=peaks.for_kind("TPU v5 lite"))


STAMPS = [{"t_s": 0.001, "t_f": 0.002, "t_e": 0.010 * i, "t_w": 0.2 + i * 1e-3}
          for i in range(1, 21)]


def test_stamp_reader():
    view = _view(PREFILL_MIX, "qwen1.5-0.5b", STAMPS, None)
    assert metrics.reader("dispatch_ms.prefill")(view) == pytest.approx(3.0)
    assert metrics.reader("dispatch_ms.prefill")(
        _view(PREFILL_MIX, "qwen1.5-0.5b", [], None)) is None


def test_prefill_step_by_hand():
    red = trace.reduce(trace.load(SMALL))
    view = _view(PREFILL_MIX, "qwen1.5-0.5b", STAMPS, red)
    st = red["steps"]["prefill_step"]
    ms = metrics.reader("prefill_step_ms.prefill")(view)
    assert ms == pytest.approx(1e3 * st["device_s"] / st["count"])
    flops = work.for_config("qwen1.5-0.5b").prefill(view.config, 16)[0]
    assert metrics.reader("step_mfu.prefill")(view) == pytest.approx(
        100 * flops * st["count"] / (st["device_s"] * 197e12))


@pytest.mark.parametrize("name", [
    "prefill_step_ms.prefill", "step_mfu.prefill",
    "device_idle_frac.prefill"])
def test_trace_readers_on_the_small_trace(name):
    red = trace.reduce(trace.load(SMALL))
    view = _view(PREFILL_MIX, "qwen1.5-0.5b", STAMPS, red)
    value = metrics.reader(name)(view)
    assert value is not None and value > 0
    if name.startswith("step_mfu"):
        assert value <= 100.0
    if name.startswith("device_idle_frac"):
        assert value < 1.0
    # nothing to read: no trace, no number (never a 0)
    assert metrics.reader(name)(_view(PREFILL_MIX, "qwen1.5-0.5b", STAMPS,
                                      None)) is None


def test_decode_context_of_an_open_mix():
    mix = dict(smoke.GEN, prompt_len=512,
               output_len={"dist": "uniform", "min": 8, "max": 32})
    ns = range(8, 33)
    ctx = [512 + i for n in ns for i in range(1, n)]
    assert metrics.decode_context(mix) == pytest.approx(np.mean(ctx))


@pytest.mark.parametrize("gaps,expect", [
    ([[0.0, 0.25], [0.5]], 0.5),
    ([[], [0.125]], 0.125),
    ([], 0.0),
    # a NaN (a token no reference row scores) is infinite wherever it is
    ([[0.5], [0.1, np.nan]], float("inf")),
    ([[np.nan], [0.1]], float("inf")),
])
def test_widest_gap(gaps, expect):
    assert check.widest([np.asarray(g, np.float32) for g in gaps]) == expect


def test_tokens_outside_the_vocabulary_are_counted():
    served = [np.array([0, 5, 127]), np.array([128]), np.array([-1, 300])]
    assert check.outside_vocab(served, 128) == 3


def test_the_pause_meter_survives_collections_in_every_thread():
    """Collections forced at nearly every allocation, while the meter's
    thread and a reader run: it ends, and reads a pause."""
    import subprocess
    import sys

    script = (
        "import gc, sys, time\n"
        "from chipbench.remote import PauseMeter\n"
        "gc.set_threshold(5, 1, 1)\n"
        "m = PauseMeter(tick=0.001).start()\n"
        "end = time.perf_counter() + 2\n"
        "while time.perf_counter() < end:\n"
        "    for x in [[] for _ in range(50)]:\n"
        "        x.append(x)\n"
        "    m.read()\n"
        "time.sleep(0.05)\n"
        "print(m.read()['late_s'] >= 0)\n"
        "m.stop()\n")
    out = subprocess.run([sys.executable, "-c", script], cwd=ROOT,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0 and out.stdout.strip() == "True", out.stderr
