"""One run of one cell: the deployment, the load, the numbers.

The parent process (this module) runs the client, the ``FuncXService``
with its TCP listener, and the load generator. It stays off JAX while the
endpoint lives: the endpoint process, spawned with
``spawn_endpoint_process`` (1 manager, 2 workers, TCP, no shared memory),
is the only one that holds the chip. Requests take the whole path:
executor → service → forwarder → TCP → endpoint → manager → worker →
fabric → jitted prefill/decode on the chip.

Order of a run: spawn the endpoint; ask it for its device (no TPU, or
fewer chips than the cell asks for, ends the run with no result); warm
both workers on the cell's one warmth key; open the window and drive the
traffic for ``seconds``; wait for what is still out; read the endpoint's
compile count and peak device memory; stop the endpoint; then, with the
chip free, run the reference over a sample of what the window served.
"""
from __future__ import annotations

import json
import os
import queue
import signal
import subprocess
import sys
import tempfile
import threading
import time
from collections import Counter, deque
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from . import remote, traffic
from .reference.weights import weight_seed

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
INSTALLER = "chipbench.remote:install"
SPAWN_TIMEOUT_S = 600.0        # the first, compiling run builds inside it
PROBE_TIMEOUT_S = 120.0        # a probe after the window
DRAIN_S = 60.0                 # a request due in the window may finish late
WARM_ROUNDS = 30
TRACE_SECONDS = 2.0            # the traced slice at the start of the window


class NoChip(RuntimeError):
    """The endpoint found no accelerator of the kind the cell needs."""


def log(*parts) -> None:
    print(*parts, flush=True)


def cache_dir() -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` when set,
    else one fixed directory inside the checkout."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def find_cell(bench: dict, name: str):
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return cell, config


@dataclass
class Window:
    """What the client saw: one record per request it sent."""
    opened: float = 0.0
    closed: float = 0.0
    requests: Dict[int, traffic.Request] = field(default_factory=dict)
    due: Dict[int, float] = field(default_factory=dict)
    sent: Dict[int, float] = field(default_factory=dict)
    done: Dict[int, float] = field(default_factory=dict)
    results: Dict[int, dict] = field(default_factory=dict)
    errors: Dict[int, str] = field(default_factory=dict)


class Cell:
    """One cell's deployment: service, endpoint process, executor."""

    def __init__(self, config: dict, mix: dict, seed: int, *,
                 trace: bool, require: str, installer: str = INSTALLER):
        self.config, self.mix, self.seed = config, mix, seed
        self.trace = trace
        self.installer = installer
        self.require = require
        self.proc = None
        self._drainer = None
        self.svc = None
        self.ex = None
        self.stderr = None

    # -- deployment --------------------------------------------------------
    def start(self, chips: int) -> None:
        """Spawn the endpoint; :class:`NoChip` if it finds no chip of the
        required kind. Probes and served tasks share the cell's one
        warmth key, so a probe never evicts a worker's warm container."""
        from repro.core import FuncXClient, FuncXService
        from repro.core.endpoint import spawn_endpoint_process
        from repro.serve import fabric

        layout = self.config["endpoint"]
        self.svc = FuncXService(heartbeat_timeout=5.0, shm=layout["shm"],
                                purge_on_get=not self.trace)
        client = FuncXClient(self.svc, self.svc.register_user("chipbench"))
        os.environ[remote.SEED_ENV] = str(weight_seed(self.seed))
        os.environ[remote.REQUIRE_ENV] = f"{self.require}:{int(chips)}"
        os.environ[remote.MANAGER_TIMEOUT_ENV] = str(
            layout.get("manager_timeout_s", ""))
        os.environ[remote.PRESET_ENV] = json.dumps(
            {"arch": self.config["arch"],
             "fields": self.config.get("set_in_preset", {})})
        os.environ["PYTHONPATH"] = os.pathsep.join(
            [str(ROOT)] + [p for p in os.environ.get("PYTHONPATH", "").split(
                os.pathsep) if p])
        self.stderr = tempfile.TemporaryFile("w+")
        try:
            self.proc, eid = spawn_endpoint_process(
                self.svc.listen(), client.endpoint_credentials(),
                name="chipbench", n_managers=layout["managers"],
                workers=layout["workers"], shm=layout["shm"],
                containers=self.installer, stderr=self.stderr)
        except RuntimeError:
            self.stderr.seek(0)
            err = self.stderr.read()
            if remote.NO_CHIP in err:
                raise NoChip(err[err.index(remote.NO_CHIP):].splitlines()[0])
            raise
        # nothing reads the endpoint's stdout after its readiness line: drain
        # it, so a chatty endpoint can never block on a full pipe
        self.stdout_tail = deque(maxlen=20)
        self._drainer = threading.Thread(target=self._drain, daemon=True,
                                         name="endpoint-stdout")
        self._drainer.start()
        self.ex = client.executor(endpoint_id=eid)
        step = self.mix["function"]
        bucket = fabric.shape_bucket(int(self.mix["prompt_len"]))
        self.key = fabric.jit_key(self.config["arch"], step, bucket)
        self.fid = client.register_function(
            remote.SERVED[step], name=f"chipbench/{step}",
            container_type=self.key)

    def _drain(self) -> None:
        for line in self.proc.stdout:
            self.stdout_tail.append(line)

    def call(self, fn, data=None, timeout: float = SPAWN_TIMEOUT_S):
        try:
            return self.ex.submit(fn, data, container_type=self.key).result(
                timeout=timeout)
        except TimeoutError:
            self.diagnose(f"{fn.__name__} got no answer in {timeout} s")
            raise

    def diagnose(self, what: str) -> None:
        """Say where a stalled run stands: the executor's harvest, the
        states of the tasks the service still holds, and every thread of
        the endpoint (its stacks go to its standard error)."""
        ex = self.ex
        harvester = ex._harvester
        log(f"stall: {what}; executor outstanding={ex.outstanding()} "
            f"harvest thread alive="
            f"{harvester is not None and harvester.is_alive()}")
        store = self.svc.tasks
        tasks = [t for t in store.get_many(store.all_ids()) if t is not None]
        log(f"stall: service task states "
            f"{dict(Counter(t.status.name for t in tasks))}")
        for t in [t for t in tasks if not t.done][:5]:
            log(f"stall: task {t.task_id} {t.status.name} stamps "
                f"{sorted(t.t)}")
        if self.proc is not None and self.proc.poll() is None:
            self.proc.send_signal(signal.SIGUSR1)
            time.sleep(2.0)

    def submit(self, req: traffic.Request):
        return self.ex.submit(self.fid, self.payload(req),
                              container_type=self.key)

    @staticmethod
    def payload(req: traffic.Request) -> dict:
        return {"tokens": req.prompt, "n_tokens": req.n_tokens, "seed": 0}

    def warm(self, requests: List[traffic.Request]) -> int:
        """Serve rounds of requests until every worker has answered warm;
        returns the rounds it took."""
        n_workers = self.config["endpoint"]["managers"] * \
            self.config["endpoint"]["workers"]
        seen = set()
        for rnd in range(1, WARM_ROUNDS + 1):
            futs = [self.submit(r) for r in requests[:2 * n_workers]]
            outs = [f.result(timeout=SPAWN_TIMEOUT_S) for f in futs]
            seen |= {o["worker"] for o in outs}
            if len(seen) >= n_workers and all(o["warm"] for o in outs):
                return rnd
        raise RuntimeError(f"warm-up left workers cold: served by {seen}")

    def stop(self) -> str:
        """Stop the endpoint and the service; the endpoint's stderr tail."""
        if self.ex is not None:
            self.ex.shutdown(wait=False)
        tail = ""
        if self.proc is not None:
            if self.proc.poll() is None:
                self.proc.send_signal(signal.SIGINT)
                try:
                    self.proc.wait(timeout=60)
                except subprocess.TimeoutExpired:
                    self.proc.kill()
                    self.proc.wait(timeout=30)
            drainer = self._drainer
            if drainer is not None:
                drainer.join(timeout=10)          # EOF once the process ended
            if drainer is None or not drainer.is_alive():
                self.proc.stdout.close()
        if self.stderr is not None:
            self.stderr.seek(0)
            tail = self.stderr.read()[-30000:]
            self.stderr.close()
        if self.svc is not None:
            self.svc.shutdown()
        return tail

    # -- the measured window -------------------------------------------------
    def drive_open(self, sched: List[traffic.Request], seconds: float) -> Window:
        """Send each request when it is due, whatever came back."""
        win = Window()
        futs = {}
        win.opened = time.perf_counter()
        for r in sched:
            due = win.opened + r.due_s
            delay = due - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            win.requests[r.index] = r
            win.due[r.index] = due
            win.sent[r.index] = time.perf_counter()
            fut = self.submit(r)
            fut.add_done_callback(
                lambda f, i=r.index: win.done.__setitem__(i, time.perf_counter()))
            futs[r.index] = fut
        win.closed = max(time.perf_counter(), win.opened + seconds)
        self._collect(futs, win)
        return win

    def drive_closed(self, pool: List[traffic.Request], seconds: float,
                     concurrency: int) -> Window:
        """Keep ``concurrency`` requests out; send the next as one ends.
        Request ``k`` of the window carries ``pool[k % len(pool)]``."""
        win = Window()
        futs = {}
        ended: "queue.Queue[int]" = queue.Queue()

        def send():
            k = len(futs)
            win.requests[k] = pool[k % len(pool)]
            win.due[k] = win.sent[k] = time.perf_counter()
            fut = self.submit(win.requests[k])

            def finished(f, i=k):
                win.done[i] = time.perf_counter()
                ended.put(i)

            fut.add_done_callback(finished)
            futs[k] = fut

        win.opened = time.perf_counter()
        close = win.opened + seconds
        for _ in range(concurrency):
            send()
        while True:
            left = close - time.perf_counter()
            if left <= 0:
                break
            try:
                ended.get(timeout=left)
            except queue.Empty:
                break
            if time.perf_counter() < close:
                send()
        win.closed = close
        self._collect(futs, win)
        return win

    def _collect(self, futs: dict, win: Window) -> None:
        deadline = win.closed + DRAIN_S
        for i, fut in futs.items():
            try:
                win.results[i] = fut.result(
                    timeout=max(deadline - time.perf_counter(), 0.01))
            except Exception as e:          # noqa: BLE001 — counted, shown
                win.errors[i] = f"{type(e).__name__}: {e}"
        if win.errors:
            self.diagnose(f"{len(win.errors)} request(s) of the window "
                          f"failed or never came back")

    def stamps(self, win: Window) -> List[dict]:
        """Task-stamp breakdowns of the window's served tasks (the service
        keeps them in a traced run)."""
        from repro.core.tasks import TaskStatus

        store = self.svc.tasks
        out = []
        for task in store.get_many(store.all_ids()):
            if (task is None or task.status != TaskStatus.SUCCESS
                    or task.function_id != self.fid
                    or task.t.get("submit", 0.0) < win.opened):
                continue
            out.append(task.latency_breakdown())
        return out


def percentile(values, q: float) -> Optional[float]:
    if not len(values):
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def run_cell(cell: dict, config: dict, mix: dict, seed: int, seconds: float,
             trace: bool, *, started: float, require: str = "tpu",
             installer: str = INSTALLER) -> dict:
    """Run the cell once; returns the numbers the result line is made of.
    Raises :class:`NoChip` before any measurement when the endpoint's
    device is not ``require`` or too few."""
    sched = traffic.schedule(mix, seed, seconds, int(config["vocab_size"]))
    dep = Cell(config, mix, seed, trace=trace, require=require,
               installer=installer)
    info: dict = {}
    try:
        dep.start(int(cell["chips"]))
        rounds = dep.warm(sched)
        dev = before = dep.call(remote.probe)
        log(f"endpoint device: {dev['platform']} {dev['kind']} "
            f"count={dev['count']}")
        log(f"compile cache: {dev['cache_dir']} entries_at_start="
            f"{dev['cache_entries_at_start']} after_warm_up="
            f"{dev['cache_entries']}")
        log(f"warm-up: {rounds} round(s), compiles so far "
            f"{before['compiles']}; manager_timeout "
            f"{before['manager_timeout']} s")
        log_dir, traced = None, None
        if trace:
            log_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            dep.call(remote.trace_start,
                     {"dir": log_dir, "seconds": min(seconds, TRACE_SECONDS)})
        setup_s = time.perf_counter() - started
        pauses = remote.PauseMeter().start()
        if mix["loop"] == "open":
            win = dep.drive_open(sched, seconds)
        else:
            win = dep.drive_closed(sched, seconds, int(mix["concurrency"]))
        pauses.stop()
        if trace:
            traced = dep.call(remote.trace_stop, timeout=PROBE_TIMEOUT_S)
        after = dep.call(remote.probe, timeout=PROBE_TIMEOUT_S)
        log("longest pause in the window, s: client/service process "
            "{late_s:.4f} (gc {gc_s:.4f}); ".format(**pauses.read()) +
            "endpoint process {late_s:.4f} (gc {gc_s:.4f})".format(
                **after["pauses"]))
        stamps = dep.stamps(win) if trace else []
    finally:
        tail = dep.stop()
        if "Traceback" in tail or "most recent call first" in tail:
            print(tail, file=sys.stderr)
    info.update(device=dev, setup_s=setup_s, window=win, stamps=stamps,
                compiles_in_window=after["compiles"] - before["compiles"],
                memory_peak_bytes=after["memory_peak_bytes"],
                log_dir=log_dir, traced=traced, schedule=sched)
    return info
