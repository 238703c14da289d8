"""Worker (paper §4.3): executes one task at a time, optionally inside a
container — here, against a warm-cached execution environment (compiled
executable). Blocking single-responsibility loop, exactly as in the paper.
"""
from __future__ import annotations

import queue
import threading
import time
import traceback
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

from ..serialization import PackedBuffer
from .spans import bind, span
from .tasks import now
from .warming import ContainerRegistry, WarmCache

_WARMUP = object()        # sentinel inbox item: pre-build a container

# Idle inbox wait. Long enough that an idle worker sleeps instead of
# spinning at 20 Hz; short enough that stop()/kill() and warm-cache reap
# deadlines are honoured promptly.
_IDLE_WAIT = 0.5


@dataclass
class WorkItem:
    task_id: str
    container_type: str
    fn: Callable
    wants_env: bool
    payload: Any
    stamps: Dict[str, float]
    # Warmth key refining the container type (DESIGN.md §10): names a
    # function-held artifact (e.g. a jit cache entry) this execution
    # creates/reuses; the worker advertises it warm after the run.
    warmth_key: str = ""


@dataclass
class WorkResult:
    task_id: str
    status: str                   # "SUCCESS" | "FAILED"
    result: Any = None
    error: Optional[str] = None
    remote_traceback: str = ""
    stamps: Dict[str, float] = None
    cold_start: bool = False
    build_time: float = 0.0
    worker_id: str = ""


class Worker(threading.Thread):
    def __init__(self, worker_id: str, registry: ContainerRegistry,
                 result_cb: Callable[[WorkResult], None],
                 cache_slots: int = 1,
                 idle_timeout: Optional[float] = None,
                 slowdown: float = 0.0):
        super().__init__(daemon=True, name=f"worker-{worker_id}")
        self.worker_id = worker_id
        self.cache = WarmCache(registry, slots=cache_slots,
                               idle_timeout=idle_timeout)
        self.result_cb = result_cb
        self.inbox: "queue.Queue" = queue.Queue(maxsize=4)
        self.busy = threading.Event()
        self.slowdown = slowdown          # straggler injection (tests)
        self.target_type: Optional[str] = None   # manager's proportional plan
        self.tasks_done = 0
        # idle/busy transition hook — the owning Manager dirties its
        # incrementally-maintained info() counters here instead of
        # re-scanning every worker per advertisement tick
        self.on_state_change: Optional[Callable[[], None]] = None
        self._stop = threading.Event()
        self._killed = False

    def _notify(self) -> None:
        cb = self.on_state_change
        if cb is not None:
            cb()

    # -- control ---------------------------------------------------------------
    def submit(self, item: WorkItem) -> None:
        self.busy.set()
        self.inbox.put(item)
        self._notify()

    def prewarm(self, container_type: str) -> None:
        self.inbox.put((_WARMUP, container_type))
        self._notify()

    def stop(self) -> None:
        self._stop.set()

    def kill(self) -> None:
        """Simulated node failure: stop without draining or reporting."""
        self._killed = True
        self._stop.set()

    @property
    def idle(self) -> bool:
        return not self.busy.is_set() and self.inbox.empty()

    def warm_types(self):
        return self.cache.warm_types()

    def _idle_wait(self) -> float:
        """How long the loop may block on the inbox: until the next warm
        container hits its idle timeout (so reaping happens on a deadline,
        not on every 20 Hz wakeup), capped at ``_IDLE_WAIT``."""
        deadline = self.cache.next_reap_deadline()
        if deadline is None:
            return _IDLE_WAIT
        return min(max(deadline - time.perf_counter(), 0.005), _IDLE_WAIT)

    # -- loop --------------------------------------------------------------------
    def run(self) -> None:
        while not self._stop.is_set():
            try:
                item = self.inbox.get(timeout=self._idle_wait())
            except queue.Empty:
                if not self.inbox.empty():
                    continue
                if self.busy.is_set():
                    self.busy.clear()
                    self._notify()
                deadline = self.cache.next_reap_deadline()
                if deadline is not None and time.perf_counter() >= deadline:
                    self.cache.reap()
                continue
            if self._killed:
                return
            if isinstance(item, tuple) and item[0] is _WARMUP:
                try:
                    self.cache.get_or_build(item[1])
                except Exception:           # noqa: BLE001 — a task of this
                    pass                    # type rebuilds and reports it
                if self.inbox.empty():
                    self.busy.clear()
                    self._notify()
                continue
            self._execute(item)
            if self.inbox.empty():
                self.busy.clear()
                self._notify()

    def _execute(self, item: WorkItem) -> None:
        stamps = dict(item.stamps)
        container, cold = None, False
        try:
            # a container build that raises (compile error, device out of
            # memory) fails this task and leaves the worker serving
            container, cold = self.cache.get_or_build(item.container_type)
            stamps["worker_start"] = now()
            if self.slowdown:
                time.sleep(self.slowdown)
            with bind(item.task_id, stamps):
                # Lazy unpack (DESIGN.md §5): the payload crossed every hop
                # as an opaque frame; this is its single decode, at the
                # consumer, just before the call. The buffer caches the
                # decoded object, so a speculative or requeued re-delivery
                # costs no re-decode.
                with span("worker.unpack"):
                    payload = item.payload
                    if isinstance(payload, PackedBuffer):
                        payload = payload.unpack()
                if item.wants_env:
                    result = item.fn(payload, container.env)
                else:
                    result = item.fn(payload)
            status, error, tb = "SUCCESS", None, ""
        except Exception as e:              # noqa: BLE001 — remote fault
            result = None
            status = "FAILED"
            error = f"{type(e).__name__}: {e}"
            tb = traceback.format_exc()
        stamps["worker_end"] = now()
        if (status == "SUCCESS" and item.warmth_key
                and item.warmth_key != item.container_type):
            # the function-held artifact (jit cache entry, ...) now lives
            # in this worker's process: advertise it like a warm container
            self.cache.note_warm(item.warmth_key)
        self.tasks_done += 1
        if self._killed:
            return                           # result lost with the node
        self.result_cb(WorkResult(
            task_id=item.task_id, status=status, result=result, error=error,
            remote_traceback=tb, stamps=stamps, cold_start=cold,
            build_time=container.build_time if cold else 0.0,
            worker_id=self.worker_id))
