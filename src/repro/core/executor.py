"""Futures-native SDK surface (DESIGN.md §8): the funcX paper's
``FuncXExecutor`` — a ``concurrent.futures``-style executor whose
batching amortizes the per-task costs that dominate FaaS latency (§5).

    ex = client.executor(endpoint_id=eid)
    fut = ex.submit(my_fn, {"x": 1})       # real concurrent.futures.Future
    fut.result()
    ex.shutdown(wait=True)

``submit`` parks the call on a client-side :class:`SubmitCoalescer`
(the mirror of the endpoint's ResultCoalescer): a lone submit flushes
inline on the caller's thread — zero added latency over ``client.run`` —
while a many-thread submit storm is drained by a dedicated flusher into
batches of ~``batch_size``, each landed with **one**
``FuncXService.submit_packed_batch`` call (token validated once, one
store lock, one pool enqueue per endpoint group → one ``TaskBatch`` wire
frame per endpoint). Payloads are packed once, on the submitting
caller's thread, via the existing pack-once fast path.

Futures resolve off the result plane's ``BatchWaiter`` machinery: one
harvest thread holds a single long-lived waiter, registers each flush's
task ids incrementally (``TaskStore.watch``), and wakes once per result
*batch*, not per task. It starts with the first outstanding future and
exits when none remain — an idle executor owns no polling thread.
Remote failures propagate as ``TaskFailure``/``TaskLost`` into the
future; ``cancel()`` before the flush removes the parked entry (the
flush skips futures whose ``set_running_or_notify_cancel`` fails);
``shutdown(wait=True)`` drains parked submissions and outstanding
futures.
"""
from __future__ import annotations

import threading
from concurrent.futures import Future, wait as _wait_futures
from typing import Any, Callable, Dict, Iterable, List, Optional

from .batching import SubmitCoalescer
from .errors import TaskFailure, TaskLost
from .tasks import TaskStatus


class FuncXExecutor:
    """``concurrent.futures``-style executor over a :class:`FuncXClient`.

    ``fn`` may be a callable (auto-registered with the service on first
    use, cached per executor) or an already-registered function id
    string. ``endpoint_id=None`` — at construction or per submit — routes
    each flush across the federation via the service's EndpointRouter.
    """

    def __init__(self, client, *, endpoint_id: Optional[str] = None,
                 container_type: Optional[str] = None,
                 warmth_key: Optional[str] = None,
                 batch_size: int = 32, linger: float = 0.002,
                 harvest_grace: float = 0.2):
        self.client = client
        self.service = client.service
        self.endpoint_id = endpoint_id
        self.container_type = container_type
        self.warmth_key = warmth_key
        self._fn_ids: Dict[Callable, str] = {}
        self._fn_lock = threading.Lock()
        self._lock = threading.Lock()
        self._futures: Dict[str, Future] = {}   # task_id → outstanding future
        self._unwatched: List[str] = []         # flushed, not yet on the waiter
        self._harvester: Optional[threading.Thread] = None
        self._work_event = threading.Event()   # new ids handed to harvest
        self.harvest_grace = harvest_grace
        self._shutdown = False
        self._cancel_parked = False
        self.coalescer = SubmitCoalescer(self._ship, batch_size=batch_size,
                                         linger=linger,
                                         outstanding=self.outstanding)
        # gauges
        self.tasks_cancelled = 0               # parked entries cancelled

    # ------------------------------------------------------------- submission
    def _function_id(self, fn) -> str:
        if isinstance(fn, str):
            return fn
        fid = self._fn_ids.get(fn)
        if fid is None:
            with self._fn_lock:
                fid = self._fn_ids.get(fn)
                if fid is None:
                    fid = self._fn_ids[fn] = \
                        self.client.register_function(fn)
        return fid

    def submit(self, fn, data: Any = None, *,
               endpoint_id: Optional[str] = None,
               container_type: Optional[str] = None,
               warmth_key: Optional[str] = None) -> Future:
        """Park one invocation on the coalescer and return its Future.
        The payload is packed here, on the caller's thread — a 16-thread
        storm packs in parallel and the flusher only groups bytes.
        ``warmth_key`` flows into the flush's RoutingContext: federation
        and manager routing both steer toward workers already holding
        the named artifact (jit cache entry, DESIGN.md §10)."""
        if self._shutdown:
            raise RuntimeError("cannot submit after shutdown")
        fid = self._function_id(fn)
        packed = self.client.pack_payload(data)
        fut: Future = Future()
        self.coalescer.add((fid, endpoint_id or self.endpoint_id, packed,
                            container_type or self.container_type,
                            warmth_key or self.warmth_key, fut))
        return fut

    def map(self, fn, payloads: Iterable[Any], *,
            endpoint_id: Optional[str] = None,
            timeout: Optional[float] = None) -> List[Any]:
        """Submit one task per payload; results in input order (the
        streaming form is plain ``concurrent.futures.as_completed`` over
        the futures from :meth:`submit`)."""
        futs = [self.submit(fn, p, endpoint_id=endpoint_id)
                for p in payloads]
        return [f.result(timeout) for f in futs]

    # -------------------------------------------------------- coalescer flush
    def _ship(self, batch: List[tuple]) -> None:
        """One coalescer flush: skip cancelled entries, land the rest with
        a single ``submit_packed_batch`` (which groups them per resolved
        endpoint), map task ids onto futures, and make sure the harvest
        thread is running. Never raises — a failed flush resolves its
        futures with the exception instead."""
        if self._cancel_parked:            # shutdown(cancel_futures=True)
            for entry in batch:
                if entry[5].cancel():
                    self.tasks_cancelled += 1
            return
        live = []
        for entry in batch:
            # a future whose cancel() landed before the flush never
            # becomes a task; everything else transitions to RUNNING
            # here, so cancel() from now on returns False
            if entry[5].set_running_or_notify_cancel():
                live.append(entry)
            else:
                self.tasks_cancelled += 1
        if not live:
            return
        try:
            tids = self.service.submit_packed_batch(
                self.client.token,
                [(fid, eid, packed, ct, wk)
                 for fid, eid, packed, ct, wk, _ in live])
        except Exception as e:             # noqa: BLE001 — resolve futures
            for entry in live:
                entry[5].set_exception(e)
            return
        with self._lock:
            for tid, entry in zip(tids, live):
                self._futures[tid] = entry[5]
            self._unwatched.extend(tids)
            self._ensure_harvester_locked()
        self._work_event.set()

    # ---------------------------------------------------------------- harvest
    def _ensure_harvester_locked(self) -> None:
        if self._harvester is None:
            t = threading.Thread(target=self._harvest_loop, daemon=True,
                                 name="executor-harvest")
            self._harvester = t
            t.start()

    @property
    def harvest_running(self) -> bool:
        return self._harvester is not None

    def outstanding(self) -> int:
        with self._lock:
            return len(self._futures) + len(self._unwatched)

    def _resolve_wave(self, store, done) -> None:
        """Resolve one waiter wake's worth of futures with two store
        round-trips — ``get_many`` + ``purge_many`` — instead of a
        wait/get/purge lock cycle per task (the same amortization
        ``get_batch_results`` does; this is where the executor beats a
        per-call ``client.run`` + ``get_result`` harvest)."""
        with self._lock:
            wave = [(tid, self._futures.pop(tid)) for tid in done
                    if tid in self._futures]
        tids = [tid for tid, _ in wave]
        try:
            tasks = store.get_many(tids)
        except Exception as e:             # noqa: BLE001 — propagate
            for _, fut in wave:
                fut.set_exception(e)
            return
        for (tid, fut), task in zip(wave, tasks):
            if task is None:               # purged underneath us
                fut.set_exception(KeyError(tid))
            elif task.status == TaskStatus.SUCCESS:
                fut.set_result(task.result_value())   # decode-once
            elif task.status == TaskStatus.LOST:
                fut.set_exception(TaskLost(task.error or "task lost"))
            else:
                fut.set_exception(TaskFailure(task.error or "task failed",
                                              task.remote_traceback))
        if self.service.purge_on_get:
            store.purge_many(tids)

    def _harvest_loop(self) -> None:
        """One long-lived BatchWaiter serves every outstanding future:
        each flush's ids are registered incrementally and a 32-result
        ResultBatch wakes this loop once. At zero outstanding it lingers
        ``harvest_grace`` seconds for the next wave (sequential lone
        submits reuse the thread instead of paying a spawn each), then
        exits — an idle executor owns no thread. The exit check and
        ``_ship``'s restart share ``self._lock``, so a racing flush
        either keeps this thread alive or starts a fresh one — never
        neither."""
        store = self.service.tasks
        waiter = store.make_waiter(())
        try:
            while True:
                with self._lock:
                    new = self._unwatched
                    self._unwatched = []
                    active = bool(new or self._futures)
                if new:
                    store.watch(waiter, new)
                if active:
                    done = waiter.wait(0.05)
                    if done:
                        self._resolve_wave(store, done)
                    continue
                # zero outstanding: linger for the next wave, then stop.
                # clear-before-check so a flush landing between the check
                # and the wait leaves the event set (no lost wakeup).
                self._work_event.clear()
                with self._lock:
                    pending = bool(self._unwatched or self._futures)
                if pending or self._work_event.wait(self.harvest_grace):
                    continue
                with self._lock:
                    if not self._unwatched and not self._futures:
                        self._harvester = None
                        return
        finally:
            store.close_waiter(waiter)

    # --------------------------------------------------------------- lifecycle
    def shutdown(self, wait: bool = True, *,
                 cancel_futures: bool = False) -> None:
        """Refuse new submissions; flush what is parked (or cancel it,
        with ``cancel_futures=True``); with ``wait=True`` block until
        every outstanding future resolved. ``wait=False`` returns after
        the final flush — results keep arriving on the harvest thread."""
        with self._lock:
            already = self._shutdown
            self._shutdown = True
        if cancel_futures:
            self._cancel_parked = True
        if not already:
            self.coalescer.close()         # final drain, ships or cancels
        if wait:
            with self._lock:
                futs = list(self._futures.values())
            _wait_futures(futs)

    def __enter__(self) -> "FuncXExecutor":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown(wait=True)
