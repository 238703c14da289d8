"""Spans (core/spans.py) and the split of ``t_w`` into host work and
device wait, through the normal submit path at the ``@smoke`` size on the
CPU: the stamps a served task brings back, and the spans a CPU profiler
session records on the worker's thread, on the trace's own clock."""
import glob
import math
import os
import subprocess
import sys
from collections import defaultdict
from pathlib import Path

import numpy as np
import pytest

from repro.core import FuncXClient, FuncXService
from repro.core.tasks import DEVICE_WAIT, FIRST_TOKEN, TOKENS, Task
from repro.serve import fabric

REPO = Path(__file__).resolve().parents[1]
SMOKE = "qwen1.5-0.5b@smoke"
WORKER_SPANS = ("worker.unpack", "fabric.put", "fabric.dispatch",
                "fabric.fetch")
ENDPOINT_SPANS = ("endpoint.recv", "endpoint.dispatch", "endpoint.flush")


@pytest.fixture
def kept():
    """A service that keeps finished tasks (their stamps) after the
    executor reads them, and a client of it."""
    svc = FuncXService(heartbeat_timeout=0.3, purge_on_get=False)
    yield svc, FuncXClient(svc, svc.register_user("u"))
    svc.shutdown()


@pytest.fixture
def serve(kept):
    """``serve(step, data)``: one request through ``executor.submit`` to a
    served worker, warm on both steps; the finished task."""
    svc, client = kept
    fabric.install(svc.containers)
    eid, agent = svc.make_endpoint(client.token, "fabric", n_managers=1,
                                   workers_per_manager=1,
                                   manager_kw={"cache_slots": 2})
    ex = client.executor(endpoint_id=eid)
    fids = {}

    def run(step, data):
        if step not in fids:
            (fids[step], _), = fabric.register_zoo(
                client, [SMOKE], step=step).values()
        before = set(svc.tasks.all_ids())
        ex.submit(fids[step], data).result(timeout=120)
        tid, = set(svc.tasks.all_ids()) - before
        return svc.tasks.get(tid)

    yield run
    ex.shutdown(wait=False)
    agent.stop()


PROMPT = np.arange(1, 17, dtype=np.int32)[None]


def _add(data):
    return data[0] + data[1]


@pytest.mark.parametrize("step,data", [
    ("prefill", {"tokens": PROMPT}),
    ("generate", {"tokens": PROMPT, "n_tokens": 4}),
])
def test_t_w_splits_into_host_work_and_device_wait(serve, step, data):
    for task in [serve(step, data) for _ in range(2)]:
        bd = task.latency_breakdown()
        assert bd["t_w_host"] >= 0 and bd["t_w_device"] > 0
        assert bd["t_w_host"] + bd["t_w_device"] == bd["t_w"]
        assert bd["t_w_device"] == pytest.approx(task.t[DEVICE_WAIT])


def test_serve_generate_stamps_its_first_token_and_count(serve):
    for n in (1, 4):
        task = serve("generate", {"tokens": PROMPT, "n_tokens": n})
        t = task.t
        assert t[TOKENS] == n
        assert t["worker_start"] < t[FIRST_TOKEN] <= t["worker_end"]
        bd = task.latency_breakdown()
        assert bd["t_w_first"] == t[FIRST_TOKEN] - t["worker_start"]
        if n == 1:
            assert math.isnan(bd["t_w_next"])
        else:
            assert bd["t_w_next"] == pytest.approx(
                (t["worker_end"] - t[FIRST_TOKEN]) / (n - 1))
            assert bd["t_w_first"] + (n - 1) * bd["t_w_next"] == \
                pytest.approx(bd["t_w"])


def test_tasks_that_generate_nothing_have_no_token_times(kept, serve):
    svc, client = kept
    task = serve("prefill", {"tokens": PROMPT})
    eid, agent = svc.make_endpoint(client.token, "plain", n_managers=1,
                                   workers_per_manager=1)
    fid = client.register_function(_add)
    try:
        tid = client.run(fid, eid, data=[1, 2])
        assert client.get_result(tid, timeout=30) == 3
    finally:
        agent.stop()
    for task in (task, client.task(tid)):
        assert FIRST_TOKEN not in task.t and TOKENS not in task.t
        bd = task.latency_breakdown()
        assert math.isnan(bd["t_w_first"]) and math.isnan(bd["t_w_next"])
        assert bd["t_w"] > 0


def test_a_plain_function_waits_on_no_device(kept):
    svc, client = kept
    eid, agent = svc.make_endpoint(client.token, "plain", n_managers=1,
                                   workers_per_manager=1)
    fid = client.register_function(_add)
    try:
        tid = client.run(fid, eid, data=[1, 2])
        assert client.get_result(tid, timeout=30) == 3
    finally:
        agent.stop()
    task = client.task(tid)
    bd = task.latency_breakdown()
    assert DEVICE_WAIT not in task.t
    assert bd["t_w_device"] == 0.0 and bd["t_w_host"] == bd["t_w"] > 0


@pytest.mark.parametrize("seed", range(4))
def test_the_split_sums_to_t_w_exactly(seed):
    rng = np.random.default_rng(seed)
    for _ in range(2000):
        start = float(rng.uniform(0, 1e5))
        t_w = float(rng.choice([rng.uniform(0, 1e-2), rng.uniform(0, 10)]))
        wait = float(rng.uniform(-0.1, 1.2)) * t_w
        task = Task("f", "e", None, "c")
        task.t.update(worker_start=start, worker_end=start + t_w)
        task.t[DEVICE_WAIT] = wait
        bd = task.latency_breakdown()
        assert bd["t_w_host"] + bd["t_w_device"] == bd["t_w"]
        assert 0 <= bd["t_w_device"] <= bd["t_w"] and bd["t_w_host"] >= 0
        assert bd["t_w_device"] == pytest.approx(
            min(max(wait, 0.0), bd["t_w"]), abs=1e-12)


def test_a_task_that_never_ran_has_no_split():
    task = Task("f", "e", None, "c")
    task.t.update(submit=1.0, worker_start=2.0)
    bd = task.latency_breakdown()
    assert math.isnan(bd["t_w_host"]) and math.isnan(bd["t_w_device"])


def _profiled_spans(log_dir):
    """``{task_id or None: [(name, start_ns, end_ns, line, stats)]}`` of the
    program's spans in the session's xplane."""
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    spans = defaultdict(list)
    names = set(WORKER_SPANS + ENDPOINT_SPANS)
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            for e in line.events:
                if e.name not in names:
                    continue
                stats = dict(e.stats)
                spans[stats.get("task_id")].append(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns,
                     (plane.name, li), stats))
    return spans


def test_spans_on_the_profiler_clock(serve, tmp_path):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 2
    opts.python_tracer_level = 0
    jobs = [("prefill", {"tokens": PROMPT}),
            ("generate", {"tokens": PROMPT, "n_tokens": 4})]
    for job in jobs:                               # compile outside it
        serve(*job)
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        tasks = [serve(*job) for job in jobs * 2]
    finally:
        jax.profiler.stop_trace()
    spans = _profiled_spans(str(tmp_path))

    offset = None
    for task, (step, data) in zip(tasks, jobs * 2):
        mine = sorted(spans[task.task_id], key=lambda s: s[1])
        names = [s[0] for s in mine]
        # leaf spans in program order, one fetch per host round trip
        assert names[:2] == ["worker.unpack", "fabric.put"]
        assert names.count("fabric.fetch") == data.get("n_tokens", 1)
        assert names.count("fabric.dispatch") == names.count("fabric.fetch")
        # all on one thread: the worker's, which runs no endpoint loop
        line, = {s[3] for s in mine}
        assert not [s for s in spans[None]
                    if s[3] == line and s[0] != "endpoint.flush"]
        # the first span carries its entry time on the stamps' clock
        first = mine[0]
        assert "perf_ns" in first[4]
        assert not any("perf_ns" in s[4] for s in mine[1:])
        if offset is None:
            offset = first[4]["perf_ns"] - first[1]
        # one task's offset places another task's stamps on the trace
        start = task.t["worker_start"] * 1e9 - offset
        assert abs(start - first[1]) < 1e6
        assert task.t[DEVICE_WAIT] * 1e9 == pytest.approx(
            sum(e - s for n, s, e, _l, _st in mine if n == "fabric.fetch"),
            abs=1e6)

    # every endpoint span appears, with no task id
    seen = {s[0] for s in spans[None]}
    assert seen == set(ENDPOINT_SPANS)
    # no two of one thread's spans overlap
    by_line = defaultdict(list)
    for group in spans.values():
        for name, s, e, line, _st in group:
            by_line[line].append((s, e, name))
    for line, evs in by_line.items():
        evs.sort()
        for (s0, e0, n0), (s1, e1, n1) in zip(evs, evs[1:]):
            assert e0 <= s1, (line, n0, n1)


def test_importing_core_leaves_jax_out():
    script = (
        "import sys\n"
        "import repro.core\n"
        "from repro.core.spans import bind, device_wait, span\n"
        "from repro.core.tasks import DEVICE_WAIT\n"
        "stamps = {}\n"
        "with bind('t', stamps):\n"
        "    with span('fabric.put'):\n"
        "        pass\n"
        "    with device_wait('fabric.fetch'):\n"
        "        pass\n"
        "    with device_wait('fabric.fetch'):\n"
        "        pass\n"
        "with device_wait('endpoint.flush'):\n"
        "    pass\n"
        "print('jax' in sys.modules, sorted(stamps) == [DEVICE_WAIT],\n"
        "      stamps[DEVICE_WAIT] > 0)\n")
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == ["False", "True", "True"]
