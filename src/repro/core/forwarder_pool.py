"""ForwarderPool (paper §4.1, multiplexed): the service-side forwarder tier.

The seed implementation ran one ``Forwarder`` per registered endpoint —
three dedicated threads each (dispatch / recv / monitor), so N endpoints
cost 3N service threads. The paper's service scales to thousands of
endpoints; thread-per-endpoint cannot. This pool keeps the exact same
per-endpoint semantics (service-side FIFO queue, batch dispatch, in-flight
tracking, heartbeat liveness, requeue-on-disconnect) but multiplexes all
endpoints over **one** dispatch loop, **one** recv loop (a ``ChannelHub``
select), and **one** monitor loop — O(1) threads for any fleet size.

Per-endpoint state lives in an ``EndpointLine``; the pool's condition
variable wakes the dispatch loop whenever any line has work.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Callable, Deque, Dict, List, Optional, Tuple

from ..serialization import SerializationError
from .comms import Channel, ChannelHub
from .protocol import (
    Ack,
    FnRequest,
    FnResponse,
    Heartbeat,
    HubFetch,
    PeerData,
    ProtocolError,
    ResolvePeer,
    ResultBatch,
    ResultMsg,
    ShmAttach,
    TaskBatch,
    TaskSpec,
    from_wire,
    to_wire,
    to_wire_parts,
)
from .routing import EndpointInfo, WarmthView
from .tasks import TaskStatus, TaskStore, now


class EndpointLine:
    """One endpoint's service-side state inside the pool.

    Exposes the slice of the old ``Forwarder`` API that callers (service,
    tests, benchmarks) observe: ``endpoint_connected``, ``queue_len()``,
    ``in_flight_count()``, ``send_rtt``, and the dispatch metrics.
    All mutation happens under the owning pool's lock.
    """

    def __init__(self, endpoint_id: str, channel: Channel,
                 lock: threading.RLock):
        self.endpoint_id = endpoint_id
        self.channel = channel
        self._lock = lock
        self.queue: Deque[str] = collections.deque()
        self.in_flight: Dict[str, float] = {}
        self.last_heartbeat = time.time()
        self.endpoint_connected = True
        self.send_rtt = 0.0             # per-message latency (benchmarks)
        self.next_send_at = 0.0         # send_rtt gate; never blocks others
        self.advertised = Heartbeat(endpoint_id=endpoint_id)
        # tasks dispatched since the last heartbeat refreshed the credit
        # advertisement — only consulted when the endpoint advertises a
        # bounded intake (an interchange, DESIGN.md §11)
        self.sent_since_credit = 0
        self.peer_addr = ""             # PeerServer address from Register
        #   ("" → endpoint runs no peer server; ResolvePeer answers no)
        # metrics
        self.dispatched = 0
        self.results_received = 0
        self.result_envelopes = 0       # ResultBatch frames (gauge: results
        #                                 per envelope → batching efficiency)
        self.requeues = 0

    def queue_len(self) -> int:
        with self._lock:
            return len(self.queue)

    def in_flight_count(self) -> int:
        with self._lock:
            return len(self.in_flight)

    def info(self) -> EndpointInfo:
        """Snapshot for the federation-level EndpointRouter."""
        adv = self.advertised
        with self._lock:
            service_queue = len(self.queue)
            in_flight = len(self.in_flight)
        warmth = WarmthView.from_heartbeat(adv)   # snapshot-local copy
        return EndpointInfo(
            endpoint_id=self.endpoint_id,
            connected=self.endpoint_connected and self.channel.connected,
            service_queue=service_queue,
            in_flight=in_flight,
            queued=adv.queued,
            idle_workers=adv.idle_workers,
            capacity=adv.capacity,
            warm_idle=warmth.idle,
            warm_total=warmth.total,
        )


class ForwarderPool:
    def __init__(
        self,
        task_store: TaskStore,
        *,
        batch_size: int = 32,
        heartbeat_timeout: float = 0.5,
        fn_resolver: Optional[Callable[[str], Tuple[bytes, bool]]] = None,
        on_shm_attach: Optional[Callable[["EndpointLine", ShmAttach],
                                         None]] = None,
        on_peer_msg: Optional[Callable[["EndpointLine", object],
                                       None]] = None,
    ):
        self.task_store = task_store
        self.batch_size = batch_size
        self.heartbeat_timeout = heartbeat_timeout
        # (function_id) -> (serialized body, wants_env); serves FnRequest
        # from remote endpoints (same-process agents call the service's
        # export hook directly and never send one).
        self.fn_resolver = fn_resolver
        # endpoint confirmed/refused a shared-memory ring attach: the
        # service owns the rings, so the swap decision lives there
        self.on_shm_attach = on_shm_attach
        # peer-plane signaling (ResolvePeer / HubFetch / relayed PeerData):
        # grant minting and relay correlation are service policy, not
        # transport — the pool only routes
        self.on_peer_msg = on_peer_msg
        # heartbeat-advertised build costs → cost-aware router feedback
        # (set by the service when its router implements observe_build)
        self.on_build_costs: Optional[Callable[[Dict[str, float]],
                                               None]] = None

        self.hub = ChannelHub()
        self._lines: Dict[str, EndpointLine] = {}
        self._lock = threading.RLock()
        self._cond = threading.Condition(self._lock)
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        # metrics (pool-wide; per-endpoint counts live on the lines)
        self.dispatched = 0
        self.results_received = 0
        self.result_envelopes = 0
        self.requeues = 0

    # ------------------------------------------------------------------ control
    def start(self) -> None:
        for name, fn in [("dispatch", self._dispatch_loop),
                         ("recv", self._recv_loop),
                         ("monitor", self._monitor_loop)]:
            t = threading.Thread(target=fn, daemon=True, name=f"pool-{name}")
            t.start()
            self._threads.append(t)

    def stop(self) -> None:
        self._stop.set()
        with self._cond:
            self._cond.notify_all()

    @property
    def healthy(self) -> bool:
        return all(t.is_alive() for t in self._threads) and \
            not self._stop.is_set()

    # -------------------------------------------------------------- membership
    def register(self, endpoint_id: str, channel: Channel) -> EndpointLine:
        line = EndpointLine(endpoint_id, channel, self._lock)
        with self._cond:
            self._lines[endpoint_id] = line
        self.hub.register(endpoint_id, channel)
        return line

    def unregister(self, endpoint_id: str) -> Optional[EndpointLine]:
        self.hub.unregister(endpoint_id)
        with self._cond:
            return self._lines.pop(endpoint_id, None)

    def reattach(self, endpoint_id: str, channel: Channel) -> EndpointLine:
        """Swap the channel under an existing line — an endpoint that lost
        its socket dialed back in. The line keeps its queue and metrics;
        everything that was in flight on the dead channel is requeued
        (requeue-on-disconnect semantics, paper §4.3), so tasks dispatched
        into the void complete after the reconnect."""
        with self._cond:
            line = self._lines[endpoint_id]
            old = line.channel
            line.channel = channel
            line.endpoint_connected = True
            line.last_heartbeat = time.time()
            self._cond.notify()
        self.hub.unregister(endpoint_id)
        self.hub.register(endpoint_id, channel)
        if old is not channel:
            old.close()
        self.requeue_in_flight(line)
        return line

    def line(self, endpoint_id: str) -> EndpointLine:
        with self._lock:
            return self._lines[endpoint_id]

    def lines(self) -> List[EndpointLine]:
        with self._lock:
            return list(self._lines.values())

    def endpoint_infos(self) -> List[EndpointInfo]:
        return [ln.info() for ln in self.lines()]

    # ------------------------------------------------------------------ intake
    def enqueue(self, endpoint_id: str, task_id: str,
                front: bool = False) -> None:
        with self._cond:
            line = self._lines[endpoint_id]
            if front:
                line.queue.appendleft(task_id)
            else:
                line.queue.append(task_id)
            self._cond.notify()

    def enqueue_many(self, endpoint_id: str, task_ids: List[str]) -> None:
        with self._cond:
            self._lines[endpoint_id].queue.extend(task_ids)
            self._cond.notify()

    # ------------------------------------------------------------------- loops
    def _sendable(self) -> List[Tuple[EndpointLine, List[str]]]:
        """Pop up to batch_size queued ids from every line that is ready to
        send. Caller must hold the lock."""
        out = []
        now_t = time.time()
        for line in self._lines.values():
            if not line.queue:
                continue
            if not line.endpoint_connected or not line.channel.connected:
                continue
            if line.send_rtt and line.next_send_at > now_t:
                continue               # emulated RTT not elapsed yet
            limit = self.batch_size
            credits = line.advertised.credits
            if credits >= 0:
                # bounded-intake endpoint (interchange): respect the
                # advertised backlog room, net of what we sent since the
                # advertisement — backpressure instead of overrun
                room = credits - line.sent_since_credit
                if room <= 0:
                    continue
                limit = min(limit, room)
            batch = []
            while line.queue and len(batch) < limit:
                batch.append(line.queue.popleft())
            out.append((line, batch))
        return out

    def _wait_timeout(self) -> float:
        """How long the dispatch loop may sleep: wake early if an
        RTT-gated line with queued work comes due sooner than the default
        poll interval. Caller must hold the lock."""
        t = 0.05
        now_t = time.time()
        for line in self._lines.values():
            if line.queue and line.send_rtt and line.next_send_at > now_t:
                t = min(t, line.next_send_at - now_t)
        return max(t, 0.001)

    def _dispatch_loop(self) -> None:
        while not self._stop.is_set():
            with self._cond:
                batches = self._sendable()
                while not batches and not self._stop.is_set():
                    self._cond.wait(timeout=self._wait_timeout())
                    batches = self._sendable()
            if self._stop.is_set():
                return
            for line, task_ids in batches:
                self._dispatch(line, task_ids)

    def _dispatch(self, line: EndpointLine, task_ids: List[str]) -> None:
        specs: List[TaskSpec] = []
        for tid, task in zip(task_ids, self.task_store.get_many(task_ids)):
            if task is None or task.done:
                continue
            task.status = TaskStatus.DISPATCHED
            task.stamp("forwarder_sent")
            specs.append(TaskSpec(task_id=tid,
                                  function_id=task.function_id,
                                  container_type=task.container_type,
                                  payload=task.payload,
                                  warmth_key=task.warmth_key))
        if not specs:
            return
        # scatter-gather send: the envelope carries segment indices and the
        # packed payload buffers ride behind it as borrowed views — no
        # payload memcpy into the envelope (DESIGN.md §7)
        env, segs = to_wire_parts(TaskBatch(tasks=specs))
        # in-flight entries land BEFORE the send: a fast endpoint can
        # return a result before this thread re-acquires the lock, and
        # the result handler must find the entry to pop
        t = time.time()
        with self._lock:
            for spec in specs:
                line.in_flight[spec.task_id] = t
        ok = line.channel.send_parts_to_endpoint(env, segs, tag="tasks")
        with self._lock:
            if ok:
                if line.send_rtt:
                    line.next_send_at = t + line.send_rtt
                line.sent_since_credit += len(specs)
                line.dispatched += len(specs)
                self.dispatched += len(specs)
            else:
                # channel refused (disconnected / dropped): requeue in order
                for spec in specs:
                    line.in_flight.pop(spec.task_id, None)
                line.queue.extendleft(reversed([s.task_id for s in specs]))

    def _recv_loop(self) -> None:
        """Drains the hub. Messages arrive *packed*; the routing tag comes
        from the buffer header (peek, no payload deserialization), and only
        the protocol envelope is decoded here — task/result payloads inside
        it stay opaque byte frames until their consumer unpacks them
        (pack-once plane, DESIGN.md §5)."""
        while not self._stop.is_set():
            for eid, buf in self.hub.poll(timeout=0.05):
                with self._lock:
                    line = self._lines.get(eid)
                if line is None:
                    continue
                try:
                    msg = from_wire(buf.unpack())
                except (ProtocolError, SerializationError):
                    continue
                if isinstance(msg, Heartbeat):
                    self._handle_heartbeat(line, msg)
                elif isinstance(msg, Ack):
                    self._handle_ack(msg)
                elif isinstance(msg, ResultBatch):
                    self._handle_result_batch(line, msg)
                elif isinstance(msg, ResultMsg):
                    # legacy lone-result envelope (hand-built messages,
                    # older agents): same path, batch of one
                    self._handle_result_batch(
                        line, ResultBatch(results=[msg]))
                elif isinstance(msg, FnRequest):
                    self._handle_fn_request(line, msg)
                elif isinstance(msg, ShmAttach):
                    cb = self.on_shm_attach
                    if cb is not None:
                        cb(line, msg)
                elif isinstance(msg, (ResolvePeer, HubFetch, PeerData)):
                    cb = self.on_peer_msg
                    if cb is not None:
                        try:
                            cb(line, msg)
                        except Exception:
                            # a malformed signaling frame must not kill
                            # the shared recv loop; the requester times out
                            pass

    def _handle_heartbeat(self, line: EndpointLine, hb: Heartbeat) -> None:
        line.last_heartbeat = time.time()
        line.advertised = hb
        if hb.credits >= 0:
            with self._lock:
                line.sent_since_credit = 0     # credit window refreshed
        # feed measured cold-build costs to a cost-aware federation
        # router (observe_build, DESIGN.md §10) — the service installs
        # the hook when its EndpointRouter can consume them
        if hb.build_costs and self.on_build_costs is not None:
            self.on_build_costs(hb.build_costs)
        if not line.endpoint_connected:
            line.endpoint_connected = True          # reconnected
            with self._cond:
                self._cond.notify()                 # queued work can flow

    def _handle_ack(self, ack: Ack) -> None:
        # one store lock round-trip for the whole acked batch
        for task in self.task_store.get_many(ack.task_ids):
            if task is not None:
                task.t.setdefault("endpoint_recv",
                                  ack.t_endpoint_recv or now())

    def _handle_result_batch(self, line: EndpointLine,
                             batch: ResultBatch) -> None:
        """Resolve a whole ResultBatch with batch-granular locking: one
        pool-lock acquisition clears every member from the in-flight map,
        one store round-trip fetches the tasks, and one ``mark_done_many``
        wakes the waiters — lock traffic per *envelope*, not per task.
        Duplicate members (batched retransmission racing a requeued
        re-execution) are dropped by the ``task.done`` check, keeping the
        exactly-once contract batch-wise."""
        for ack in batch.acks:
            self._handle_ack(ack)
        if not batch.results:
            return
        line.result_envelopes += 1
        self.result_envelopes += 1
        with self._lock:
            for res in batch.results:
                line.in_flight.pop(res.task_id, None)
        tasks = self.task_store.get_many(
            [res.task_id for res in batch.results])
        done_ids: List[str] = []
        for res, task in zip(batch.results, tasks):
            if task is None or task.done:
                continue               # purged or duplicate — drop
            task.t.update(res.stamps)
            task.cold_start = res.cold_start
            task.worker_id = res.worker_id
            task.manager_id = res.manager_id
            if res.status == "SUCCESS":
                task.result = res.result
                task.status = TaskStatus.SUCCESS
            elif res.status == "LOST":
                task.error = res.error
                task.status = TaskStatus.LOST
            else:
                task.error = res.error
                task.remote_traceback = res.remote_traceback
                task.status = TaskStatus.FAILED
            task.stamp("result_stored")
            done_ids.append(res.task_id)
        line.results_received += len(done_ids)
        self.results_received += len(done_ids)
        self.task_store.mark_done_many(done_ids)

    def _handle_fn_request(self, line: EndpointLine, req: FnRequest) -> None:
        """Remote endpoint pulling a function body. Errors travel back in
        the response — the requesting fetch fails that one task's staging,
        never this shared recv loop."""
        if self.fn_resolver is None:
            resp = FnResponse(function_id=req.function_id,
                              error="service has no function resolver")
        else:
            try:
                blob, wants_env = self.fn_resolver(req.function_id)
                resp = FnResponse(function_id=req.function_id,
                                  payload=blob, wants_env=wants_env)
            except Exception as e:
                resp = FnResponse(function_id=req.function_id,
                                  error=f"{type(e).__name__}: {e}")
        line.channel.send_to_endpoint(to_wire(resp), tag="fn")

    def _monitor_loop(self) -> None:
        """Heartbeat-based endpoint liveness (paper: 30 s default; scaled
        down here). On loss: requeue that endpoint's in-flight tasks."""
        while not self._stop.is_set():
            time.sleep(self.heartbeat_timeout / 4)
            cutoff = time.time() - self.heartbeat_timeout
            for line in self.lines():
                if line.endpoint_connected and line.last_heartbeat < cutoff:
                    line.endpoint_connected = False
                    self.requeue_in_flight(line)

    def requeue_in_flight(self, line: EndpointLine) -> None:
        """Put the line's dispatched-but-unresolved tasks back at the head
        of its queue, preserving dispatch order (FIFO is kept: in-flight
        tasks left the queue before anything currently in it)."""
        with self._cond:
            pending = list(line.in_flight.keys())
            line.in_flight.clear()
            requeued = []
            for tid in pending:
                try:
                    task = self.task_store.get(tid)
                except KeyError:
                    continue
                if not task.done:
                    task.status = TaskStatus.PENDING
                    requeued.append(tid)
            line.queue.extendleft(reversed(requeued))
            line.requeues += len(requeued)
            self.requeues += len(requeued)
            self._cond.notify()
