"""Task model + lifecycle (paper Fig. 2).

Timestamps intentionally mirror the paper's latency decomposition (§7.1):
t_s (service), t_f (forwarder), t_e (endpoint/manager queuing), t_w (worker
execution) — `latency_breakdown()` reproduces Fig. 3 from any finished task.
`t_w` splits into the seconds the worker waited on the device (the fabric's
blocking reads, summed under ``DEVICE_WAIT`` by `spans.device_wait`) and the rest,
its host work. A generating function also stamps its first token
(``FIRST_TOKEN``) and its token count (``TOKENS``): time to first token and
seconds per later token; on an MoE model also the held experts its decode
steps read per expert layer and step (``EXPERTS_READ``).
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
import uuid
from dataclasses import dataclass, field
from enum import Enum
from typing import Any, Dict, Iterable, List, Optional, Sequence, Set

# uuid4 costs a urandom syscall (~50 µs) per call — measurable overhead at
# thousands of submissions per second, all on the serial submit path. One
# random prefix per process keeps ids globally unique; a counter keeps
# them unique in-process.
_ID_PREFIX = uuid.uuid4().hex[:12]
_ID_COUNTER = itertools.count()


def new_task_id() -> str:
    return f"{_ID_PREFIX}-{next(_ID_COUNTER):08x}"


class TaskStatus(Enum):
    PENDING = "PENDING"            # accepted by service, queued
    DISPATCHED = "DISPATCHED"      # forwarder → endpoint
    MANAGER_QUEUED = "MANAGER_QUEUED"
    RUNNING = "RUNNING"
    SUCCESS = "SUCCESS"
    FAILED = "FAILED"
    LOST = "LOST"                  # retry budget exhausted


TERMINAL = {TaskStatus.SUCCESS, TaskStatus.FAILED, TaskStatus.LOST}


def now() -> float:
    return time.perf_counter()


# Stamp entry holding a duration, not a time: the seconds the worker spent
# in the fabric's blocking device→host reads (``fabric.fetch`` spans).
DEVICE_WAIT = "device_wait"
# Stamp entries of a generating function: the moment its first token's
# fetch returned (a time), and how many tokens it fetched (a count).
FIRST_TOKEN = "first_token"
TOKENS = "tokens"
# A count of a generating MoE model: held experts read per expert layer and
# decode step.
EXPERTS_READ = "experts_read"


@dataclass
class Task:
    function_id: str
    endpoint_id: str
    payload: Any                       # PackedBuffer (pack-once plane) or a
    #                                    plain object on legacy/test paths
    container_type: str                # compile signature / container image
    warmth_key: str = ""               # refined warmth key (DESIGN.md §10)
    task_id: str = field(default_factory=new_task_id)
    status: TaskStatus = TaskStatus.PENDING
    result: Any = None
    error: Optional[str] = None
    remote_traceback: str = ""
    retries: int = 0
    max_retries: int = 2
    # latency instrumentation (Fig. 3)
    t: Dict[str, float] = field(default_factory=dict)
    # warm/cold accounting (Fig. 7)
    cold_start: bool = False
    worker_id: Optional[str] = None
    manager_id: Optional[str] = None

    def stamp(self, name: str) -> None:
        self.t[name] = now()

    def latency_breakdown(self) -> Dict[str, float]:
        """Seconds in each tier, funcX Fig. 3 decomposition."""
        t = self.t
        get = lambda a, b: max(t.get(b, 0.0) - t.get(a, 0.0), 0.0) \
            if a in t and b in t else float("nan")
        t_w = get("worker_start", "worker_end")
        # both parts as differences from t_w, so that they sum to it exactly
        t_w_host = t_w - min(max(t.get(DEVICE_WAIT, 0.0), 0.0), t_w)
        n = t.get(TOKENS, 0)
        t_w_next = (get(FIRST_TOKEN, "worker_end") / (n - 1) if n > 1
                    else float("nan"))
        return {
            "t_s": get("submit", "service_queued"),
            "t_f": get("service_queued", "endpoint_recv"),
            "t_e": get("endpoint_recv", "worker_start"),
            "t_w": t_w,
            "t_w_host": t_w_host,
            "t_w_device": t_w - t_w_host,
            # generation: worker-side time to the first token, and seconds
            # per later token
            "t_w_first": get("worker_start", FIRST_TOKEN),
            "t_w_next": t_w_next,
            EXPERTS_READ: t.get(EXPERTS_READ, float("nan")),
            "t_r": get("worker_end", "result_stored"),
            "total": get("submit", "result_stored"),
        }

    def result_value(self) -> Any:
        """The decoded result. Results arrive as opaque PackedBuffers and
        stay packed at rest; the first read decodes once and *replaces*
        the buffer with the object — retaining both the wire bytes and
        the decoded value (e.g. under purge_on_get=False) would double
        result memory for nothing."""
        from ..serialization import PackedBuffer
        if isinstance(self.result, PackedBuffer):
            self.result = self.result.unpack()
        return self.result

    @property
    def done(self) -> bool:
        return self.status in TERMINAL


class BatchWaiter:
    """One registration over N task ids, woken batch-wise.

    The pre-batch harvest loop cost N sequential ``Event.wait`` + lock
    round-trips; a waiter registers once, and every ``mark_done_many``
    touching its ids appends them to ``_fired`` and sets one event — so a
    32-result batch wakes the harvester **once**, not 32 times. Obtain via
    :meth:`TaskStore.make_waiter`, release via :meth:`TaskStore.close_waiter`
    (or use :meth:`TaskStore.wait_any` for the one-shot form).
    """

    __slots__ = ("_store", "event", "_fired", "watching")

    def __init__(self, store: "TaskStore"):
        self._store = store
        self.event = threading.Event()
        self._fired: collections.deque = collections.deque()
        self.watching: Set[str] = set()

    def wait(self, timeout: Optional[float]) -> List[str]:
        """Block until ≥1 watched task completes; return the newly
        completed ids (in completion order). Empty list on timeout."""
        if not self.event.wait(timeout):
            return []
        with self._store._lock:
            out = list(self._fired)
            self._fired.clear()
            self.event.clear()
        return out


class TaskStore:
    """Service-side task table (the paper's Redis hashset analogue).

    Bulk entry points (``put_many`` / ``get_many`` / ``mark_done_many`` /
    ``purge_many``) make store traffic proportional to *batches*, not
    tasks: the ForwarderPool resolves a whole ``ResultBatch`` and the
    client harvests a whole submission under one lock round-trip each
    (DESIGN.md §6)."""

    def __init__(self):
        self._tasks: Dict[str, Task] = {}
        self._lock = threading.RLock()
        # Completion record. Events are allocated lazily — only for ids
        # someone actually waits on with `wait()` — because the batched
        # harvest path (BatchWaiter) needs no per-task Event at all, and
        # an Event per submitted task is measurable allocation churn.
        self._done: Set[str] = set()
        self._events: Dict[str, threading.Event] = {}
        # task_id -> batch waiters watching it (removed on completion or
        # close_waiter, so the dict only holds live registrations)
        self._watchers: Dict[str, List[BatchWaiter]] = {}

    def put(self, task: Task) -> None:
        with self._lock:
            self._tasks[task.task_id] = task

    def put_many(self, tasks: Iterable[Task]) -> None:
        with self._lock:
            for task in tasks:
                self._tasks[task.task_id] = task

    def get(self, task_id: str) -> Task:
        with self._lock:
            return self._tasks[task_id]

    def get_many(self, task_ids: Sequence[str]) -> List[Optional[Task]]:
        """One lock round-trip for a whole batch; unknown ids yield None
        (a purged/duplicate result is the caller's drop decision)."""
        with self._lock:
            return [self._tasks.get(t) for t in task_ids]

    def mark_done(self, task_id: str) -> None:
        self.mark_done_many((task_id,))

    def mark_done_many(self, task_ids: Sequence[str]) -> None:
        """Complete a batch under one lock acquisition: record each id
        done, set its event if anyone allocated one, and wake each
        registered batch waiter exactly once. All of it happens *inside*
        the lock — a waiter registering concurrently either sees the done
        record or is on the watcher list; no lost-wakeup window."""
        if not task_ids:
            return
        with self._lock:
            for tid in task_ids:
                self._done.add(tid)
                ev = self._events.get(tid)
                if ev is not None:
                    ev.set()
                for w in self._watchers.pop(tid, ()):
                    w.watching.discard(tid)
                    w._fired.append(tid)
                    w.event.set()

    def wait(self, task_id: str, timeout: float) -> bool:
        with self._lock:
            if task_id in self._done:
                return True
            ev = self._events.get(task_id)
            if ev is None:
                ev = self._events[task_id] = threading.Event()
        return ev.wait(timeout)

    # -- batch-aware waiting (DESIGN.md §6) --------------------------------
    def make_waiter(self, task_ids: Iterable[str]) -> BatchWaiter:
        """Register a :class:`BatchWaiter` over ``task_ids``. Tasks already
        done land in its fired queue immediately."""
        w = BatchWaiter(self)
        self.watch(w, task_ids)
        return w

    def watch(self, w: BatchWaiter, task_ids: Iterable[str]) -> None:
        """Register additional ids on an existing waiter — the incremental
        form of :meth:`make_waiter`, for harvesters whose watch set grows
        while they wait (the executor's harvest thread registers each
        flush's task ids on its one long-lived waiter, DESIGN.md §8).
        Ids already done land in the fired queue immediately."""
        with self._lock:
            for tid in task_ids:
                if tid in self._done:
                    w._fired.append(tid)
                    continue
                self._watchers.setdefault(tid, []).append(w)
                w.watching.add(tid)
            if w._fired:
                w.event.set()

    def close_waiter(self, w: BatchWaiter) -> None:
        with self._lock:
            for tid in w.watching:
                lst = self._watchers.get(tid)
                if lst is not None:
                    try:
                        lst.remove(w)
                    except ValueError:
                        pass
                    if not lst:
                        del self._watchers[tid]
            w.watching.clear()

    def wait_any(self, task_ids: Iterable[str],
                 timeout: Optional[float]) -> List[str]:
        """Block until at least one of ``task_ids`` is done (or timeout);
        returns the completed ids seen by this call. One-shot form of
        :meth:`make_waiter` for callers without a harvest loop."""
        w = self.make_waiter(task_ids)
        try:
            return w.wait(timeout)
        finally:
            self.close_waiter(w)

    def purge(self, task_id: str) -> None:
        """Paper: results are purged once retrieved / after a period."""
        with self._lock:
            self._tasks.pop(task_id, None)
            self._done.discard(task_id)
            self._events.pop(task_id, None)

    def purge_many(self, task_ids: Sequence[str]) -> None:
        with self._lock:
            for tid in task_ids:
                self._tasks.pop(tid, None)
                self._done.discard(tid)
                self._events.pop(tid, None)

    def all_ids(self):
        with self._lock:
            return list(self._tasks.keys())

    def __len__(self) -> int:
        with self._lock:
            return len(self._tasks)
