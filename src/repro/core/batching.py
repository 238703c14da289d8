"""Batching (paper §4.6) + beyond-paper request coalescing.

The paper's two batching forms live elsewhere in the runtime:
  - *internal batching*: ForwarderPool.batch_size + Manager.prefetch (managers
    request many tasks on behalf of their workers);
  - *user-facing batching*: FuncXService.submit_batch / client.batch_run.

This module adds two more:

  - **SubmitCoalescer** — the client-side mirror of the endpoint's
    ResultCoalescer (DESIGN.md §8): submissions parked by many caller
    threads are drained into batched flushes, so the "millions of small
    callers" shape pays service/wire cost per *flush*, not per task.
    Used by :class:`~repro.core.executor.FuncXExecutor`.
  - **dynamic request coalescing** (``DynamicBatcher``) — concurrent
    invocations of the same function within a small window are stacked
    into one batched execution (one compiled program run for N requests)
    and the results are fanned back out. This is what turns the FaaS
    layer into a batched model server.
"""
from __future__ import annotations

import collections
import threading
import time
from concurrent.futures import Future
from typing import Any, Callable, Deque, List, Optional, Sequence, Tuple

import numpy as np


class SubmitCoalescer:
    """Adaptive micro-batching for the submit path (DESIGN.md §8).

    Entries are opaque to the coalescer — it owns *when* batches ship,
    the caller's ``ship(entries)`` callback owns *how* (the executor
    groups them per resolved endpoint and lands them with one
    ``submit_packed_batch``). Two regimes, the same policy as the result
    plane's ResultCoalescer:

    - **idle line** — a submission arriving alone, with nothing else
      parked and nothing outstanding (``outstanding()`` is the
      executor's count of unresolved futures — the submit-side analogue
      of the result coalescer's results-still-to-come signal), flushes
      inline on the caller's own thread (no handoff, no linger, no
      timer): a lone ``executor.submit`` pays zero added latency over a
      direct ``client.run``;
    - **loaded line** (other submissions parked, or futures already in
      flight — a wave in progress) — the producer just appends
      (deque.append is atomic under the GIL; the kick Event is touched
      through an ``is_set()`` fast path) and the dedicated flusher
      thread drains everything pending in batches of at most
      ``batch_size``, holding an under-full batch open for a bounded
      *linger* so it fills toward ``batch_size``. A 16-thread submit
      storm thus ships ~batch_size tasks per flush.

    ``ship`` must not raise — the executor resolves per-entry futures
    itself; an exception escaping here would kill the flusher and strand
    parked work.
    """

    def __init__(self, ship: Callable[[List[Any]], None], *,
                 batch_size: int = 32, linger: float = 0.002,
                 outstanding: Optional[Callable[[], int]] = None):
        self._ship = ship
        self.batch_size = batch_size
        self.linger = linger
        self._outstanding = outstanding if outstanding is not None \
            else (lambda: 0)
        self._parked: Deque[Any] = collections.deque()
        self._kick = threading.Event()       # "pending work" signal
        self._flush_lock = threading.Lock()  # one drainer at a time
        self._stop = threading.Event()
        # gauges (submit-plane acceptance: flushes/task << 1 under storm)
        self.flushes = 0                     # ship() calls
        self._thread = threading.Thread(target=self._flush_loop, daemon=True,
                                        name="submit-coalescer")
        self._thread.start()

    def close(self) -> None:
        """Stop the flusher, then drain whatever is parked — every
        accepted submission is shipped (or cancelled by the executor's
        ship callback), never silently dropped."""
        self._stop.set()
        self._kick.set()
        with self._flush_lock:
            self._drain()
        self._thread.join(timeout=2.0)

    def pending(self) -> int:
        return len(self._parked)

    # -- producers ---------------------------------------------------------
    def add(self, entry: Any) -> None:
        self._parked.append(entry)
        if self._stop.is_set():
            # flusher is gone (executor shutting down, a racing submit
            # slipped in): drain synchronously — blocking acquire, because
            # a kick nobody listens to would strand this entry
            with self._flush_lock:
                self._drain()
            return
        if len(self._parked) == 1 and self._outstanding() <= 0:
            # idle line: this submission is alone and no wave is in
            # flight — ship on this thread right now. If the flusher
            # happens to hold the lock it is actively draining and will
            # recheck; the kick covers the race window.
            if self._flush_lock.acquire(blocking=False):
                try:
                    self._drain(max_flushes=1)
                finally:
                    self._flush_lock.release()
            else:
                self._kick.set()
            return
        if not self._kick.is_set():          # lock-free in steady state —
            self._kick.set()                 # under storm the kick stays set

    # -- the flusher -------------------------------------------------------
    def _flush_loop(self) -> None:
        while not self._stop.is_set():
            if not self._parked:
                self._kick.wait(0.05)
                self._kick.clear()
                continue
            if self.linger > 0 and len(self._parked) < self.batch_size:
                # under-full batch with callers still appending: let it
                # fill. A plain bounded sleep — a lone submit never waits
                # on it because the idle line flushes inline on the
                # caller's thread instead of landing here.
                self._stop.wait(self.linger)
            with self._flush_lock:
                self._drain(max_flushes=1)

    def _drain(self, max_flushes: Optional[int] = None) -> None:
        flushed = 0
        while self._parked and (max_flushes is None
                                or flushed < max_flushes):
            batch: List[Any] = []
            while self._parked and len(batch) < self.batch_size:
                try:
                    batch.append(self._parked.popleft())
                except IndexError:         # racing drainer emptied it
                    break
            if not batch:
                return
            self._ship(batch)
            self.flushes += 1
            flushed += 1


def stack_arrays(payloads: Sequence[Any]) -> Any:
    """Default stack: dict-of-arrays payloads are concatenated on axis 0;
    scalar fields (e.g. "n_tokens") must agree and pass through."""
    first = payloads[0]
    if isinstance(first, dict):
        out = {}
        for k in first:
            v0 = np.asarray(first[k])
            if v0.ndim == 0:
                vals = {np.asarray(p[k]).item() for p in payloads}
                if len(vals) != 1:
                    raise ValueError(
                        f"cannot coalesce: scalar field {k!r} differs "
                        f"across requests ({vals})")
                out[k] = first[k]
            else:
                out[k] = np.concatenate([np.asarray(p[k]) for p in payloads],
                                        axis=0)
        return out
    return np.concatenate([np.asarray(p) for p in payloads], axis=0)


def split_arrays(result: Any, sizes: Sequence[int]) -> List[Any]:
    """Default split: slice axis 0 back into the per-request sizes;
    scalars replicate."""
    bounds = np.cumsum([0] + list(sizes))
    def cut(x, i):
        arr = np.asarray(x)
        if arr.ndim == 0:
            return x
        return arr[bounds[i]:bounds[i + 1]]
    if isinstance(result, dict):
        return [{k: cut(v, i) for k, v in result.items()}
                for i in range(len(sizes))]
    return [cut(result, i) for i in range(len(sizes))]


class DynamicBatcher:
    """Coalesce concurrent requests to one function into batched tasks.

    Requests are queued up to ``max_batch`` or ``max_wait`` seconds; each
    flush submits ONE task whose payload is the stacked batch. Downstream
    the whole funcX path (routing, warm containers) sees a single task, so
    per-task overhead is amortized — the §7.5 effect, applied per-request.
    """

    def __init__(
        self,
        submit_fn: Callable[[Any], str],          # payload → task_id
        result_fn: Callable[[str, float], Any],   # task_id → result
        *,
        max_batch: int = 8,
        max_wait: float = 0.01,
        batch_dim_key: Optional[str] = "tokens",
        stack_fn: Callable = stack_arrays,
        split_fn: Callable = split_arrays,
        result_timeout: float = 60.0,
    ):
        self.submit_fn = submit_fn
        self.result_fn = result_fn
        self.max_batch = max_batch
        self.max_wait = max_wait
        self.batch_dim_key = batch_dim_key
        self.stack_fn = stack_fn
        self.split_fn = split_fn
        self.result_timeout = result_timeout
        self._pending: List[Tuple[Any, Future, int]] = []
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="dyn-batcher")
        self._thread.start()
        self.batches_sent = 0
        self.requests_sent = 0

    def _size_of(self, payload: Any) -> int:
        if isinstance(payload, dict) and self.batch_dim_key in payload:
            return int(np.asarray(payload[self.batch_dim_key]).shape[0])
        return 1

    def submit(self, payload: Any) -> Future:
        fut: Future = Future()
        with self._cond:
            self._pending.append((payload, fut, self._size_of(payload)))
            if len(self._pending) >= self.max_batch:
                self._cond.notify()
        return fut

    def close(self) -> None:
        self._stop.set()
        with self._cond:
            self._cond.notify_all()
        self._thread.join(timeout=2.0)

    def _loop(self) -> None:
        while not self._stop.is_set():
            with self._cond:
                if not self._pending:
                    self._cond.wait(timeout=self.max_wait)
                    if not self._pending:
                        continue
                # window: let the batch fill up briefly
                deadline = time.perf_counter() + self.max_wait
                while (len(self._pending) < self.max_batch
                       and time.perf_counter() < deadline):
                    self._cond.wait(timeout=max(
                        deadline - time.perf_counter(), 0.0005))
                batch = self._pending[:self.max_batch]
                self._pending = self._pending[self.max_batch:]
            self._flush(batch)

    def _flush(self, batch) -> None:
        payloads = [b[0] for b in batch]
        futures = [b[1] for b in batch]
        sizes = [b[2] for b in batch]
        try:
            stacked = self.stack_fn(payloads) if len(payloads) > 1 \
                else payloads[0]
            task_id = self.submit_fn(stacked)
            self.batches_sent += 1
            self.requests_sent += len(payloads)
            result = self.result_fn(task_id, self.result_timeout)
            parts = (self.split_fn(result, sizes) if len(payloads) > 1
                     else [result])
            for fut, part in zip(futures, parts):
                fut.set_result(part)
        except Exception as e:          # noqa: BLE001 — propagate to callers
            for fut in futures:
                if not fut.done():
                    fut.set_exception(e)
