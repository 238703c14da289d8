"""The funcX endpoint agent (paper §4.3).

Deployed "on" a compute resource (here: hosting a set of manager/worker
threads and, for model-serving functions, a device mesh). Responsibilities,
mirroring the paper:

- registers with the service; receives tasks from its forwarder channel and
  acks receipt (hierarchical queuing: tasks are cached at each layer until
  the next layer acknowledges);
- routes tasks to managers via a pluggable, warming-aware router (§6.2);
- collects results and returns them to the forwarder;
- heartbeats to the forwarder pool, advertising queue depth and
  warm-container state (the service's federation-level router feeds on
  these); detects *lost managers* via their heartbeats and re-executes
  their in-flight tasks (§4.3 fault tolerance);
- optional speculative re-execution of stragglers (beyond paper);
- optional elastic provisioning strategy (§6.3).

Deployment modes (DESIGN.md §2): the agent is transport-agnostic. In the
same-process mode it shares a ``Channel`` (LocalTransport) with the
service; in the federated mode this module doubles as the **endpoint-agent
entrypoint** —

    python -m repro.core.endpoint --connect HOST:PORT --token @token.json

— dialing the service's TCP listener, registering over the wire
(``Register``/``RegisterAck`` handshake), fetching function bodies on
demand (``FnRequest``/``FnResponse``), and surviving service restarts by
re-dialing + re-registering under the same endpoint id (the service then
requeues whatever was in flight).
"""
from __future__ import annotations

import argparse
import collections
import itertools
import pickle
import socket as _socket
import threading
import time
from time import monotonic as _monotonic
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

from ..data import (
    KVStore,
    SERVICE_PAYLOAD_LIMIT,
    TransferService,
    resolve_inputs,
    stage_outputs,
)
from ..serialization import PackedBuffer, SerializationError, pack_buffer
from .comms import (
    Channel,
    ShmRing,
    ShmTransport,
    TcpTransport,
    parse_hostport,
)
from .errors import RegistrationError
from .manager import Manager
from .protocol import (
    Ack,
    FnRequest,
    FnResponse,
    Heartbeat,
    PeerData,
    PeerGet,
    ProtocolError,
    Register,
    RegisterAck,
    ResolvePeerAck,
    ResultBatch,
    ResultMsg,
    ShmAttach,
    TaskBatch,
    TaskSpec,
    from_wire,
    to_wire,
    to_wire_parts,
)
from .routing import Router, RoutingContext, WarmthView, make_router
from .spans import span
from .tasks import now
from .warming import ContainerRegistry
from .worker import WorkItem, WorkResult


class _BoundedSet:
    """Generation-bounded membership set — the duplicate-drop record for
    shipped results. A long-running agent used to grow ``_completed``
    forever; recency is all dedup needs (a duplicate arrives within a
    requeue/speculation window, not a million tasks later), so entries
    age out by generation rotation: adds go to the current generation,
    membership checks both, and when the current one reaches ``cap/2``
    it becomes the previous (dropping the old previous). The retention
    window is therefore between cap/2 and cap recent ids.

    The hot path is lock-free: dict reads and ``setdefault`` are atomic
    under the GIL, and the insert *is* the membership test (two managers
    completing the same speculated task race on one ``setdefault``; the
    loser sees the winner's token). A lock exists only to serialize the
    rare rotation."""

    __slots__ = ("cap", "_cur", "_prev", "_rotate_lock")

    def __init__(self, cap: int):
        self.cap = max(cap, 2)
        self._cur: Dict[str, object] = {}
        self._prev: Dict[str, object] = {}
        self._rotate_lock = threading.Lock()

    def add(self, key: str) -> bool:
        """True if newly added, False if already present."""
        if key in self._prev:
            return False
        token = object()
        if self._cur.setdefault(key, token) is not token:
            return False                   # lost the race / already there
        # re-check prev: a rotation between our prev-read and the
        # setdefault can move a racing winner's entry into _prev while
        # our insert lands in the fresh _cur — token identity tells our
        # own rotated entry apart from a true duplicate
        pv = self._prev.get(key)
        if pv is not None and pv is not token:
            return False
        if len(self._cur) > self.cap // 2 and \
                self._rotate_lock.acquire(blocking=False):
            try:
                if len(self._cur) > self.cap // 2:
                    self._prev = self._cur
                    self._cur = {}
            finally:
                self._rotate_lock.release()
        return True

    def __contains__(self, key: str) -> bool:
        return key in self._cur or key in self._prev

    def __len__(self) -> int:
        return len(self._cur) + len(self._prev)


class ResultCoalescer:
    """Adaptive micro-batching for the return path (DESIGN.md §6).

    Two regimes, chosen per completion:

    - **idle line** — the lone result's own thread flushes immediately
      (no handoff, no linger, no timer): single-task latency is
      untouched;
    - **loaded line** (more results outstanding upstream) — the producer
      just appends and a dedicated flusher thread drains everything
      pending into :class:`ResultBatch` envelopes of at most
      ``batch_size`` results, holding an under-full envelope open for a
      bounded *linger* so it fills toward ``batch_size``. Producers —
      worker callbacks and the agent recv loop — are never blocked by
      pack/send/linger work, so result shipping cannot stall task intake
      or execution; envelopes-per-task drops toward 1/batch_size.

    Receipt ``Ack``s coalesce the same way: they ride whatever envelope
    flushes next (an ack-only envelope never lingers — receipt stamps are
    carried data, so coalescing costs nothing, but delivery shouldn't
    idle-wait on a result that may be seconds away).

    Envelopes the channel refuses are parked in ``_unsent`` *as built*
    and retransmitted batch-wise by :meth:`flush_unsent` (heartbeat loop)
    once the link returns — the service drops per-member duplicates by
    task id, so a retransmitted batch racing a requeued re-execution
    stays exactly-once.
    """

    def __init__(self, send: Callable[[dict, list], bool], *,
                 batch_size: int = 32, linger: float = 0.002,
                 outstanding: Optional[Callable[[], int]] = None):
        self._send = send
        self.batch_size = batch_size
        self.linger = linger
        self._outstanding = outstanding if outstanding is not None \
            else (lambda: 0)
        # Producer path is lock-free: deque.append is atomic under the
        # GIL, and the kick Event is touched only through an `is_set()`
        # fast-path read. An earlier design funneled every completion
        # through one condition variable — with dozens of worker threads
        # on a small core count, stack samples showed the whole fleet
        # convoying on that lock while throughput collapsed.
        self._results: Deque[ResultMsg] = collections.deque()
        self._acks: Deque[Ack] = collections.deque()
        self._kick = threading.Event()     # "pending work" signal
        self._flush_lock = threading.Lock()    # one drainer at a time
        self._unsent: Deque[Tuple[dict, list]] = collections.deque()
        self._stop = threading.Event()
        # gauges (result-plane acceptance: envelopes-per-task < 1 under load)
        self.envelopes_sent = 0            # envelopes the channel accepted
        self.result_envelopes = 0          # ...of which carried ≥1 result
        self.results_sent = 0
        self.envelopes_parked = 0          # refused by the link, queued for
        #                                    retransmission
        self._thread = threading.Thread(target=self._flush_loop, daemon=True,
                                        name="result-coalescer")
        self._thread.start()

    def close(self) -> None:
        """Stop the flusher, then drain whatever is pending — every
        completed result is sent or parked, never silently dropped (the
        pre-coalescer path sent synchronously and had no stop window)."""
        self._stop.set()
        self._kick.set()
        with self._flush_lock:
            self._drain()

    # -- producers ---------------------------------------------------------
    def add_result(self, msg: ResultMsg) -> None:
        self._results.append(msg)
        if self._stop.is_set():
            # flusher is gone (agent stopping, workers still completing):
            # drain synchronously — blocking acquire, because falling back
            # to a kick nobody listens to would drop this result
            with self._flush_lock:
                self._drain()
            return
        if self._outstanding() <= 0:
            # idle line (or the tail of a load wave): ship on this thread
            # right now — no handoff, no linger. If the flusher happens to
            # hold the lock it is actively draining and will recheck; the
            # kick covers the race window.
            if self._flush_lock.acquire(blocking=False):
                try:
                    self._drain()
                finally:
                    self._flush_lock.release()
            else:
                self._kick.set()
            return
        if not self._kick.is_set():        # lock-free in steady state —
            self._kick.set()               # under load the kick stays set

    def add_ack(self, ack: Ack) -> None:
        """Acks never flush inline — the recv loop must get back to task
        intake; they ride the flusher's next envelope."""
        self._acks.append(ack)
        if not self._kick.is_set():
            self._kick.set()

    # -- the flusher -------------------------------------------------------
    def _flush_loop(self) -> None:
        while not self._stop.is_set():
            if not self._results and not self._acks:
                self._kick.wait(0.05)
                self._kick.clear()
                continue
            if (self.linger > 0 and self._results
                    and len(self._results) < self.batch_size
                    and self._outstanding() > 0):
                # under-full envelope with more results on the way: let it
                # fill. A plain bounded sleep — the tail never waits on it
                # because the last completion (outstanding == 0) flushes
                # inline on its own thread while we sleep outside the lock.
                self._stop.wait(self.linger)
            with self._flush_lock:
                self._drain(max_envelopes=1)

    def _drain(self, max_envelopes: Optional[int] = None) -> None:
        """Pop pending results/acks into envelopes and ship. Caller holds
        ``_flush_lock`` (single consumer); producers may append
        concurrently and anything landing after the final empty check is
        picked up by the flusher's next pass (kick/backstop)."""
        n_env = 0
        while True:
            n = min(len(self._results), self.batch_size)
            results = [self._results.popleft() for _ in range(n)]
            acks = []
            while self._acks:
                acks.append(self._acks.popleft())
            if not results and not acks:
                return
            with span("endpoint.flush"):
                # scatter-gather: large packed results ride behind the
                # envelope as borrowed segments — no memcpy into it (§7)
                env, segs = to_wire_parts(ResultBatch(results=results,
                                                      acks=acks))
                sent = self._send(env, segs)
            if sent:
                self.envelopes_sent += 1
                self.result_envelopes += 1 if results else 0
                self.results_sent += len(results)
            else:
                self._unsent.append((env, segs))
                self.envelopes_parked += 1
            n_env += 1
            if max_envelopes is not None and n_env >= max_envelopes:
                return

    # -- retransmission (single consumer: the heartbeat loop) --------------
    def flush_unsent(self) -> None:
        """Retransmit parked envelopes in completion order until the link
        refuses again. Runs under ``_flush_lock`` so the gauge counters
        never race a concurrent drain (they feed the acceptance metrics;
        this path is cold)."""
        if not self._unsent:
            return
        with self._flush_lock:
            while self._unsent:
                env, segs = self._unsent[0]
                if not self._send(env, segs):
                    return
                self._unsent.popleft()
                self.envelopes_sent += 1
                n = len(env.get("results", ()))
                self.result_envelopes += 1 if n else 0
                self.results_sent += n

    @property
    def unsent_count(self) -> int:
        return len(self._unsent)


class EndpointAgent:
    def __init__(
        self,
        endpoint_id: str,
        channel: Channel,
        fetch_function: Callable[[str], Tuple[Callable, bool]],
        *,
        registry: Optional[ContainerRegistry] = None,
        router: str | Router = "warming_aware",
        store: Optional[KVStore] = None,
        transfer: Optional[TransferService] = None,
        heartbeat_interval: float = 0.05,
        manager_timeout: float = 1.0,
        max_retries: int = 2,
        speculation: bool = False,
        speculation_factor: float = 4.0,
        speculation_min: float = 0.25,
        stage_results: bool = True,
        stage_limit: int = SERVICE_PAYLOAD_LIMIT,
        extra_handler: Optional[Callable[[Any], None]] = None,
        result_batch: int = 32,
        result_linger: float = 0.002,
        dedup_capacity: int = 16384,
        dispatched_ttl: float = 900.0,
        peer_server: Optional[Any] = None,
        peer_client: Optional[Any] = None,
    ):
        self.endpoint_id = endpoint_id
        self.channel = channel
        self.fetch_function = fetch_function
        self.registry = registry or ContainerRegistry()
        self.router = (router if isinstance(router, Router)
                       else make_router(router))
        self.store = store
        self.transfer = transfer
        self.heartbeat_interval = heartbeat_interval
        self.manager_timeout = manager_timeout
        self.max_retries = max_retries
        self.speculation = speculation
        self.speculation_factor = speculation_factor
        self.speculation_min = speculation_min
        self.stage_results = stage_results
        # Stage-out threshold: results whose packed size exceeds it are
        # parked in the local store and travel as DataRefs. Defaults to
        # the paper's 10 MB service limit; shuffle-style workloads (and
        # the p2p benchmarks) lower it so intermediates become refs and
        # cross endpoint-to-endpoint instead of transiting the hub.
        self.stage_limit = stage_limit
        # Non-task wire messages (FnResponse, RegisterAck on a re-dial)
        # are routed here — the remote runner's hook into the recv loop.
        self.extra_handler = extra_handler
        # Peer data plane (DESIGN.md §9): the server answers other
        # endpoints' direct fetches; the client resolves cross-endpoint
        # DataRefs at stage-in. Its signaling (ResolvePeer/HubFetch) rides
        # this agent's hub channel.
        self.peer_server = peer_server
        self.peer_client = peer_client
        if peer_client is not None and peer_client.signal is None:
            peer_client.signal = self._send_signal

        self.managers: Dict[str, Manager] = {}
        self._managers_lock = threading.RLock()
        self._mgr_counter = itertools.count()

        self._queue: "collections.deque" = collections.deque()
        self._queue_lock = threading.Lock()
        self._queue_cond = threading.Condition(self._queue_lock)
        self._dispatch_parked = False      # dispatch waiting for free room

        self._fn_cache: Dict[str, Tuple[Callable, bool]] = {}
        self._retries: Dict[str, int] = {}
        # Duplicate-drop record, LRU-bounded (a long-running agent must
        # not grow per-task state forever; recency is all dedup needs).
        self._completed = _BoundedSet(dedup_capacity)
        self._dispatched_at: Dict[str, Tuple[float, TaskSpec, str]] = {}
        self.dispatched_ttl = dispatched_ttl
        self._next_sweep = _monotonic() + 5.0
        self._durations: collections.deque = collections.deque(maxlen=256)
        # Batched return path (DESIGN.md §6): results and receipt acks
        # coalesce into ResultBatch envelopes; envelopes the link refuses
        # are parked inside the coalescer and retransmitted by the
        # heartbeat loop once the link is back. Without that parking, a
        # result produced during an outage would be lost forever — the
        # task is already in _completed, so re-execution after the
        # requeue-on-disconnect would be dropped as a duplicate.
        self.coalescer = ResultCoalescer(
            self._ship_envelope, batch_size=result_batch,
            linger=result_linger, outstanding=self._outstanding)

        # Heartbeat merge cache: the 20 Hz loop re-merges the per-manager
        # warm/load dicts only when some manager's state version moved —
        # an idle or steady fleet costs one tuple compare per beat, not a
        # full Manager.info() scan + dict merge.
        self._hb_key: Optional[tuple] = None
        self._hb_state: Tuple[int, int, int, Dict[str, int], Dict[str, int]] \
            = (0, 0, 0, {}, {})
        # Per-warmth-key cold-build cost EWMA, fed by completed results
        # and advertised on the next heartbeat (Heartbeat.build_costs) —
        # the service's cost-aware federation router learns actual build
        # costs instead of guessing (DESIGN.md §10).
        self._build_costs: Dict[str, float] = {}
        self._build_costs_lock = threading.Lock()

        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self.strategy = None
        # metrics
        self.tasks_received = 0
        self.tasks_reexecuted = 0
        self.speculative_dispatches = 0

    # ------------------------------------------------------------------ control
    def start(self) -> None:
        for name, fn in [("recv", self._recv_loop),
                         ("dispatch", self._dispatch_loop),
                         ("heartbeat", self._heartbeat_loop),
                         ("monitor", self._monitor_loop)]:
            t = threading.Thread(target=fn, daemon=True,
                                 name=f"ep-{self.endpoint_id}-{name}")
            t.start()
            self._threads.append(t)

    def stop(self) -> None:
        self._stop.set()
        self.coalescer.close()
        if self.strategy is not None:
            self.strategy.stop()
        if self.peer_server is not None:
            self.peer_server.close()
        if self.peer_client is not None:
            self.peer_client.close()
        with self._managers_lock:
            for m in self.managers.values():
                m.stop()
        with self._queue_cond:
            self._queue_cond.notify_all()

    # ---------------------------------------------------------------- managers
    def add_manager(self, n_workers: int = 4, **kw) -> Manager:
        mid = f"{self.endpoint_id}/m{next(self._mgr_counter)}"
        m = Manager(mid, n_workers, self.registry, self._on_result, **kw)
        m.start()
        with self._managers_lock:
            self.managers[mid] = m
        return m

    def remove_manager(self, manager_id: str) -> None:
        with self._managers_lock:
            m = self.managers.pop(manager_id, None)
        if m is not None:
            m.stop()

    def kill_manager(self, manager_id: str) -> None:
        """Test hook: simulated node failure."""
        with self._managers_lock:
            m = self.managers.get(manager_id)
        if m is not None:
            m.kill()

    def _alive_managers(self) -> List[Manager]:
        with self._managers_lock:
            return [m for m in self.managers.values() if m.alive]

    # ------------------------------------------------------------------ metrics
    def pending_tasks(self) -> int:
        with self._queue_lock:
            q = len(self._queue)
        return q + sum(m.inbox.qsize() for m in self._alive_managers())

    def idle_workers(self) -> int:
        return sum(m.info().idle_workers for m in self._alive_managers())

    def block_idle(self, manager_ids: List[str]) -> bool:
        with self._managers_lock:
            ms = [self.managers.get(i) for i in manager_ids]
        return all(m is not None and m.alive and
                   m.info().idle_workers == len(m.workers) and
                   m.inbox.qsize() == 0 for m in ms)

    # ------------------------------------------------------------------- loops
    def _recv_loop(self) -> None:
        while not self._stop.is_set():
            wire = self.channel.recv_at_endpoint(timeout=0.05)
            if wire is None:
                continue
            env, _tag = wire
            with span("endpoint.recv"):
                try:
                    msg = from_wire(env)
                except (ProtocolError, SerializationError):
                    continue       # poison message: drop, keep the loop
                if isinstance(msg, TaskBatch):
                    t_recv = now()
                    for spec in msg.tasks:
                        spec.stamps["endpoint_recv"] = t_recv
                    self._enqueue_batch(msg.tasks)
                    # receipt ack rides the next result envelope (or its
                    # own immediately if none is in flight) — coalesced
                    # return path
                    self.coalescer.add_ack(
                        Ack(task_ids=[s.task_id for s in msg.tasks],
                            t_endpoint_recv=t_recv))
                    continue
            if isinstance(msg, PeerGet):
                # hub-relay serving: the service pulls a key from our
                # store over the already-authenticated hub channel
                self._serve_hub_get(msg)
            elif (isinstance(msg, (ResolvePeerAck, PeerData))
                  and self.peer_client is not None
                  and self.peer_client.handle_signal(msg)):
                pass                   # matched a waiting peer fetch
            elif self.extra_handler is not None:
                try:
                    self.extra_handler(msg)
                except Exception:
                    pass               # a bad handler never kills recv

    def _send_signal(self, msg: Any) -> bool:
        """PeerClient's signaling sender: one message to the service."""
        return self.channel.send_to_service(to_wire(msg), tag="peer")

    def _serve_hub_get(self, msg: PeerGet) -> None:
        """Answer a relayed fetch (rung 3 of the fallback ladder): no
        token check — the hub channel authenticated at Register."""
        if self.store is None:
            reply = PeerData(req_id=msg.req_id, key=msg.key, ok=False,
                             error="endpoint has no store")
        else:
            try:
                data = self.store.get_raw(msg.key)
                reply = PeerData(req_id=msg.req_id, key=msg.key, ok=True,
                                 data=data)
            except KeyError:
                reply = PeerData(req_id=msg.req_id, key=msg.key, ok=False,
                                 error=f"no such key: {msg.key}")
            except Exception as e:     # noqa: BLE001 — report, serve on
                reply = PeerData(req_id=msg.req_id, key=msg.key, ok=False,
                                 error=f"{type(e).__name__}: {e}")
        env, segs = to_wire_parts(reply)
        self.channel.send_parts_to_service(env, segs, tag="peer")

    def _enqueue(self, spec: TaskSpec, front: bool = False) -> None:
        self.tasks_received += 1
        with self._queue_cond:
            if front:
                self._queue.appendleft(spec)
            else:
                self._queue.append(spec)
            self._queue_cond.notify()

    def _enqueue_batch(self, specs: List[TaskSpec]) -> None:
        """One queue-lock acquisition per received TaskBatch — the recv
        loop used to take it once per member spec, contending with the
        dispatch loop 32× per envelope."""
        self.tasks_received += len(specs)
        with self._queue_cond:
            self._queue.extend(specs)
            self._queue_cond.notify()

    def _resolve_fn(self, function_id: str) -> Tuple[Callable, bool]:
        if function_id not in self._fn_cache:
            self._fn_cache[function_id] = self.fetch_function(function_id)
        return self._fn_cache[function_id]

    def _make_item(self, spec: TaskSpec) -> WorkItem:
        # requeued items after manager loss carry their resolved fn
        if spec.resolved is not None:
            fn, wants_env = spec.resolved
            payload = spec.payload
        else:
            fn, wants_env = self._resolve_fn(spec.function_id)
            payload = spec.payload
            if self.store is not None:
                if isinstance(payload, PackedBuffer):
                    # Pack-once plane: the payload stays an opaque frame
                    # unless it *can* contain DataRefs. Refs only survive
                    # serialization via pickle (nd/msgpack/json reject the
                    # dataclass), so the header method — no payload decode
                    # — decides whether stage-in must look inside.
                    if payload.method == "pickle":
                        payload = resolve_inputs(
                            payload.unpack(), self.endpoint_id,
                            self.store, self.transfer,
                            peer=self.peer_client)
                else:
                    payload = resolve_inputs(payload, self.endpoint_id,
                                             self.store, self.transfer,
                                             peer=self.peer_client)
        return WorkItem(
            task_id=spec.task_id,
            container_type=spec.container_type,
            fn=fn, wants_env=wants_env, payload=payload,
            stamps=dict(spec.stamps),
            warmth_key=spec.warmth_key)

    def _dispatch_loop(self) -> None:
        """Routes queued tasks to managers. Manager state (warm types, free
        room) is snapshotted once per iteration and updated locally while a
        whole batch of queued tasks is routed against it — amortizing the
        snapshot cost is what sustains >1k tasks/s per agent (§7.2.3)."""
        while not self._stop.is_set():
            with self._queue_cond:
                while not self._queue and not self._stop.is_set():
                    self._queue_cond.wait(timeout=0.1)
                if self._stop.is_set():
                    return
                batch = []
                while self._queue and len(batch) < 256:
                    batch.append(self._queue.popleft())

            with span("endpoint.dispatch"):
                leftovers, failed = self._route(batch)
            # after the span: a failure may flush its envelope inline
            for task_id, error in failed:
                self._send_failure(task_id, error)
            if leftovers:
                # saturated: park the overflow and wait for a completion
                # (worker callbacks notify the cond) instead of polling —
                # a freed worker resumes dispatch immediately, an idle
                # wait costs nothing
                self._dispatch_parked = True
                with self._queue_cond:
                    for spec in reversed(leftovers):
                        self._queue.appendleft(spec)
                    self._queue_cond.wait(0.002)
                self._dispatch_parked = False

    def _route(self, batch: List[TaskSpec]
               ) -> Tuple[List[TaskSpec], List[Tuple[str, str]]]:
        """One routing pass over ``batch`` against one snapshot of the
        managers; returns the specs that found no room and the
        ``(task_id, error)`` of those that could not be staged."""
        managers = self._alive_managers()
        infos = [m.info() for m in managers]
        by_id = {m.manager_id: m for m in managers}
        # room derives from the same snapshot — Manager.room() would
        # re-scan every worker a second time per cycle, and this loop
        # is the serial feed stage (§7.2.3 hot path)
        room = {inf.manager_id:
                max(inf.capacity + by_id[inf.manager_id].prefetch
                    - inf.queued, 0)
                for inf in infos}
        per_manager: Dict[str, list] = {}
        leftovers, failed = [], []
        for spec in batch:
            ctx = RoutingContext(warmth_key=spec.warmth_key or None,
                                 container_type=spec.container_type)
            target = self.router.route(ctx, infos)
            if target is None or room.get(target, 0) <= 0:
                # the router's choice is saturated: requeue and retry
                # against a fresh snapshot (never override the policy
                # with first-fit — that would erase warm affinity)
                leftovers.append(spec)
                continue
            room[target] -= 1
            for inf in infos:          # keep the snapshot coherent
                if inf.manager_id == target:
                    inf.queued += 1
                    view = inf.warmth
                    for key in ctx.warmth_keys:
                        if view.warm_idle(key) > 0:
                            view.note_pick(key)
                            break
                    inf.idle_workers = max(inf.idle_workers - 1, 0)
                    break
            try:
                item = self._make_item(spec)
            except Exception as e:         # fn fetch / stage-in failure
                failed.append((spec.task_id,
                               f"staging: {type(e).__name__}: {e}"))
                continue
            self._dispatched_at[item.task_id] = (
                time.perf_counter(), spec, target)
            per_manager.setdefault(target, []).append(item)
        for mid, items in per_manager.items():
            by_id[mid].submit_batch(items)
        return leftovers, failed

    def _on_result(self, manager_id: str, res: WorkResult) -> None:
        if not self._completed.add(res.task_id):
            return                 # duplicate (speculation / requeue) — drop
        self._retries.pop(res.task_id, None)
        disp = self._dispatched_at.pop(res.task_id, None)
        if disp is not None:
            self._durations.append(time.perf_counter() - disp[0])
            if res.cold_start and res.build_time > 0.0:
                spec = disp[1]
                self._observe_build(spec.warmth_key or spec.container_type,
                                    res.build_time)
        # a worker just freed: wake the dispatch loop iff it parked
        # overflow waiting for room (plain flag read keeps the common
        # case lock-free — grabbing the queue lock on every completion
        # would contend with the dispatch loop itself)
        if self._dispatch_parked:
            with self._queue_cond:
                self._queue_cond.notify()
        result = res.result
        if res.status == "SUCCESS":
            # Pack the result exactly once (DESIGN.md §5). The same bytes
            # serve the stage-out size decision, the store write (if the
            # result is parked behind a DataRef), and the wire frame; the
            # service stores them opaquely and get_result decodes once.
            try:
                packed = pack_buffer(result, tag="ret")
            except Exception as e:
                # Unserializable result. A store with object semantics
                # (DeviceStore) can still park the *live* object behind a
                # DataRef — the pre-PR escape hatch for device-resident
                # results; otherwise the task fails with the real reason.
                staged = None
                if self.stage_results and self.store is not None:
                    try:
                        staged = stage_outputs(
                            result, self.endpoint_id, self.store,
                            key_prefix=f"task/{res.task_id}",
                            location=self._peer_location())
                    except Exception:
                        staged = None
                if staged is None or staged is result:
                    self._send_failure(
                        res.task_id,
                        f"result serialization: {type(e).__name__}: {e}")
                    return
                self._send_result(ResultMsg(
                    task_id=res.task_id, status=res.status,
                    result=pack_buffer(staged, tag="ret"),
                    error=res.error, remote_traceback=res.remote_traceback,
                    stamps=res.stamps, cold_start=res.cold_start,
                    build_time=res.build_time, worker_id=res.worker_id,
                    manager_id=manager_id))
                return
            if (self.stage_results and self.store is not None
                    and len(packed) > self.stage_limit):
                staged = stage_outputs(result, self.endpoint_id, self.store,
                                       key_prefix=f"task/{res.task_id}",
                                       packed=packed,
                                       limit=self.stage_limit,
                                       location=self._peer_location())
                packed = pack_buffer(staged, tag="ret")   # tiny DataRef
            result = packed
        self._send_result(ResultMsg(
            task_id=res.task_id, status=res.status, result=result,
            error=res.error, remote_traceback=res.remote_traceback,
            stamps=res.stamps, cold_start=res.cold_start,
            build_time=res.build_time, worker_id=res.worker_id,
            manager_id=manager_id))

    def _observe_build(self, key: str, seconds: float) -> None:
        """Cold-build feedback, both tiers (fixes the dead observe_build
        hook): the agent's own router learns immediately; the service's
        federation router learns from the EWMA advertised in the next
        heartbeat's ``build_costs``."""
        observe = getattr(self.router, "observe_build", None)
        if observe is not None:
            observe(key, seconds)
        with self._build_costs_lock:
            prev = self._build_costs.get(key)
            self._build_costs[key] = (seconds if prev is None
                                      else 0.8 * prev + 0.2 * seconds)

    def _peer_location(self) -> str:
        """Producer address hint stamped into outgoing DataRefs."""
        srv = self.peer_server
        return srv.address if srv is not None else ""

    def _send_failure(self, task_id: str, error: str,
                      status: str = "FAILED") -> None:
        self._completed.add(task_id)
        self._retries.pop(task_id, None)
        self._send_result(ResultMsg(
            task_id=task_id, status=status, error=error))

    def _send_result(self, msg: ResultMsg) -> None:
        """Hand one outcome to the result coalescer (DESIGN.md §6): it
        ships immediately on an idle line, rides a ResultBatch under
        load, and is parked for batch-wise retransmission if the link
        refuses (the service drops duplicates by task id, so a
        retransmit racing a requeued re-execution stays exactly-once)."""
        self.coalescer.add_result(msg)

    def _ship_envelope(self, env: dict, segments: list) -> bool:
        return self.channel.send_parts_to_service(env, segments,
                                                  tag="results")

    def _outstanding(self) -> int:
        """Results still expected imminently — the coalescer's linger
        gate. Lock-free advisory reads: both containers shrink to zero
        when the line goes idle, which is the only answer that matters."""
        return len(self._dispatched_at) + len(self._queue)

    def _heartbeat_loop(self) -> None:
        while not self._stop.is_set():
            self.coalescer.flush_unsent()
            self.channel.send_to_service(to_wire(self._heartbeat()), tag="hb")
            time.sleep(self.heartbeat_interval)

    def _heartbeat(self) -> Heartbeat:
        """Liveness + load/warm advertisement (consumed by the service's
        federation-level EndpointRouter). The merged dicts are rebuilt
        only when a manager's version stamp moved since the last beat."""
        managers = self._alive_managers()
        key = tuple((m.manager_id, m.version) for m in managers)
        if key != self._hb_key:
            views = []
            capacity = idle = queued = 0
            for m in managers:
                inf = m.info()
                capacity += inf.capacity
                idle += inf.idle_workers
                queued += inf.queued
                views.append(inf.warmth)
            merged = WarmthView.merge(views)
            self._hb_state = (capacity, idle, queued,
                              merged.idle, merged.total)
            self._hb_key = key
        capacity, idle, queued, warm_idle, warm_total = self._hb_state
        with self._queue_lock:
            queued += len(self._queue)
        # store inventory advertisement (peer plane): O(1) counter reads;
        # the version stamp lets the service invalidate peer grants for
        # producers whose store has mutated since the grant was minted
        sv = sk = sb = 0
        if self.store is not None:
            try:
                inv = self.store.inventory()
                sv, sk, sb = inv.version, inv.keys, inv.nbytes
            except Exception:
                pass
        with self._build_costs_lock:
            build_costs = dict(self._build_costs)
        return Heartbeat(endpoint_id=self.endpoint_id, ts=time.time(),
                         queued=queued, idle_workers=idle, capacity=capacity,
                         warm_idle=warm_idle, warm_total=warm_total,
                         build_costs=build_costs,
                         store_version=sv, store_keys=sk, store_bytes=sb)

    # -- fault tolerance: lost managers & stragglers --------------------------
    def _monitor_loop(self) -> None:
        while not self._stop.is_set():
            time.sleep(self.heartbeat_interval)
            self._check_lost_managers()
            if self.speculation:
                self._check_stragglers()
            if _monotonic() >= self._next_sweep:
                self._sweep_dispatched()
                self._next_sweep = _monotonic() + 5.0

    def _sweep_dispatched(self) -> None:
        """Evict stale ``_dispatched_at`` entries: tasks whose result
        already shipped (defensive — the happy path pops on completion)
        and tasks in flight longer than ``dispatched_ttl`` (a wedged
        worker would otherwise pin its entry — and the straggler
        detector's interest in it — forever)."""
        cutoff = time.perf_counter() - self.dispatched_ttl
        for task_id, (t0, _spec, _mid) in list(self._dispatched_at.items()):
            if task_id in self._completed or t0 < cutoff:
                self._dispatched_at.pop(task_id, None)

    def _check_lost_managers(self) -> None:
        cutoff = time.perf_counter() - self.manager_timeout
        with self._managers_lock:
            items = list(self.managers.items())
        for mid, m in items:
            if m.alive and m.last_heartbeat >= cutoff:
                continue
            if not m.alive or m.last_heartbeat < cutoff:
                # paper §4.3: lost tasks are re-executed (if permitted)
                lost = m.in_flight()
                with self._managers_lock:
                    self.managers.pop(mid, None)
                m.stop()
                for item in lost:
                    if item.task_id in self._completed:
                        continue
                    self._dispatched_at.pop(item.task_id, None)
                    retries = self._retries.get(item.task_id, 0) + 1
                    self._retries[item.task_id] = retries
                    if retries > self.max_retries:
                        self._send_failure(
                            item.task_id,
                            f"lost after {retries - 1} retries "
                            f"(manager {mid} failed)", status="LOST")
                    else:
                        self.tasks_reexecuted += 1
                        self._enqueue(TaskSpec(
                            task_id=item.task_id, function_id="",
                            container_type=item.container_type,
                            warmth_key=item.warmth_key,
                            payload=item.payload, stamps=item.stamps,
                            resolved=(item.fn, item.wants_env)), front=True)

    def _check_stragglers(self) -> None:
        if len(self._durations) < 4:
            return
        mean = sum(self._durations) / len(self._durations)
        threshold = max(self.speculation_min, self.speculation_factor * mean)
        now_s = time.perf_counter()
        for task_id, (t0, spec, mid) in list(self._dispatched_at.items()):
            if task_id in self._completed:
                continue
            if now_s - t0 > threshold:
                # speculative duplicate on a different manager
                others = [m for m in self._alive_managers()
                          if m.manager_id != mid and m.room() > 0]
                if not others:
                    continue
                try:
                    item = self._make_item(spec)
                except Exception:
                    continue
                others[0].submit_batch([item])
                self.speculative_dispatches += 1
                # push threshold forward so we don't spam duplicates
                self._dispatched_at[task_id] = (now_s, spec, mid)


# ---------------------------------------------------------------------------
# Federated deployment: the endpoint-agent entrypoint (TcpTransport side).
# ---------------------------------------------------------------------------

def demo_noop(data):
    """Module-level demo function: resolvable by reference from any
    process with ``repro`` on its path (plain pickle ships module-level
    functions by name — the cross-process analogue of funcX's serialized
    function bodies)."""
    return None


def demo_square(data):
    x = data["x"] if isinstance(data, dict) else data
    return x * x


def demo_sleep(data):
    time.sleep(float(data.get("s", 0.0)) if isinstance(data, dict) else 0.0)
    return None


def demo_produce(data):
    """Mint an ``n``-byte blob whose content encodes ``seed`` — returned
    whole so the agent's stage-out turns it into a DataRef whenever it
    exceeds the stage limit (peer-plane benchmarks & examples)."""
    n = int(data.get("n", 65536))
    seed = int(data.get("seed", 0))
    return bytes([seed % 251]) * n


def demo_gather(data):
    """Sum the sizes of ``parts`` — each element arrives as real bytes
    because stage-in resolved any DataRefs before execution."""
    return sum(len(p) for p in data["parts"])


def spawn_endpoint_process(address, token: str, *,
                           name: str = "remote-endpoint",
                           n_managers: int = 1, workers: int = 4,
                           shm: bool = True, peer: bool = True,
                           store_kind: str = "memory",
                           stage_limit: Optional[int] = None,
                           containers: str = "", stderr=None):
    """Spawn ``python -m repro.core.endpoint`` as a child process and block
    until it prints its readiness line. Returns ``(proc, endpoint_id)``.

    The one place the spawn recipe lives (benchmarks, tests, and examples
    all call it): PYTHONPATH gains this package's ``src`` root so the
    child resolves ``repro`` no matter the caller's cwd, and ``token`` may
    be the raw credential string or an ``@file`` reference.
    """
    import os
    import subprocess
    import sys
    import tempfile
    if not isinstance(address, str):
        address = f"{address[0]}:{address[1]}"
    src_dir = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ)
    env["PYTHONPATH"] = src_dir + os.pathsep + env.get("PYTHONPATH", "")
    # stderr goes to an unbounded temp file, not a pipe: a chatty child
    # can never fill a pipe buffer and wedge, and the capture is still
    # readable when the readiness line never appears
    capture = tempfile.TemporaryFile("w+") if stderr is None else None
    argv = [sys.executable, "-m", "repro.core.endpoint",
            "--connect", address, "--token", token, "--name", name,
            "--managers", str(n_managers), "--workers", str(workers),
            "--store", store_kind]
    if stage_limit is not None:
        argv += ["--stage-limit", str(stage_limit)]
    if containers:
        argv += ["--containers", containers]
    if not shm:
        argv.append("--no-shm")
    if not peer:
        argv.append("--no-peer")
    proc = subprocess.Popen(
        argv,
        env=env, stdout=subprocess.PIPE,
        stderr=capture if capture is not None else stderr, text=True)
    line = (proc.stdout.readline() or "").strip()
    if not line.startswith("ENDPOINT_READY"):
        proc.terminate()
        err = ""
        if capture is not None:
            proc.wait(timeout=5)
            capture.seek(0)
            err = capture.read()
        raise RuntimeError(
            f"endpoint subprocess failed (got {line!r}): {err[-2000:]}")
    if capture is not None:
        capture.close()                # child keeps its own fd
    return proc, line.split()[1]


class WireFunctionClient:
    """Endpoint-side function fetch over the channel.

    ``fetch`` is the agent's ``fetch_function`` hook: it sends an
    ``FnRequest`` and blocks until the matching ``FnResponse`` arrives via
    :meth:`handle_response` (wired into the agent recv loop through
    ``extra_handler``). Requests are re-sent about once a second until
    answered, so a request lost to a link drop is recovered after the
    re-dial instead of hanging the fetch.
    """

    def __init__(self, channel: Channel, timeout: float = 15.0):
        self.channel = channel
        self.timeout = timeout
        self._lock = threading.Lock()
        self._pending: Dict[str, dict] = {}

    def fetch(self, function_id: str) -> Tuple[Callable, bool]:
        with self._lock:
            box = self._pending.get(function_id)
            if box is None:
                box = {"event": threading.Event(), "resp": None}
                self._pending[function_id] = box
        deadline = time.time() + self.timeout
        next_send = 0.0
        try:
            while not box["event"].is_set():
                now_t = time.time()
                if now_t >= deadline:
                    raise RegistrationError(
                        f"function fetch timed out: {function_id}")
                if now_t >= next_send:
                    ok = self.channel.send_to_service(
                        to_wire(FnRequest(function_id=function_id)),
                        tag="fn")
                    next_send = now_t + (1.0 if ok else 0.1)
                box["event"].wait(0.1)
        finally:
            with self._lock:
                self._pending.pop(function_id, None)
        resp: FnResponse = box["resp"]
        if resp.error:
            raise RegistrationError(
                f"service refused function {function_id}: {resp.error}")
        fn = pickle.loads(resp.payload)
        return fn, resp.wants_env

    def handle_response(self, resp: FnResponse) -> None:
        with self._lock:
            box = self._pending.get(resp.function_id)
        if box is not None:
            box["resp"] = resp
            box["event"].set()


class RemoteEndpointRunner:
    """Owns one federated endpoint: dial → register → run the agent.

    The TcpTransport re-dials on its own after any connection loss; this
    runner's ``on_connect`` hook re-sends ``Register`` with the already
    assigned endpoint id, and the service answers by swapping the new
    channel under the endpoint's line and requeueing its in-flight tasks —
    so a service listener restart costs retransmission, never task loss.
    """

    def __init__(self, address: "str | Tuple[str, int]", token: str, *,
                 name: str = "remote-endpoint", n_managers: int = 1,
                 workers_per_manager: int = 4, router: str = "warming_aware",
                 heartbeat_interval: float = 0.05,
                 register_timeout: float = 30.0,
                 shm: bool = True,
                 peer: bool = True,
                 peer_host: str = "127.0.0.1",
                 manager_kw: Optional[dict] = None, **agent_kw):
        self.address = (parse_hostport(address)
                        if isinstance(address, str) else address)
        self._token = token
        self.name = name
        self.n_managers = n_managers
        self.workers_per_manager = workers_per_manager
        self.router = router
        self.heartbeat_interval = heartbeat_interval
        self.register_timeout = register_timeout
        self.shm = shm                 # advertise shared-memory support
        self.shm_attached = False
        self.peer = peer               # run the peer data plane (DESIGN §9)
        self.peer_host = peer_host
        self.manager_kw = manager_kw or {}
        self.agent_kw = agent_kw
        self.endpoint_id: Optional[str] = None
        self.channel: Optional[Channel] = None
        self.transport: Optional[TcpTransport] = None
        self.agent: Optional[EndpointAgent] = None
        self.fns: Optional[WireFunctionClient] = None
        self.peer_server = None
        self.peer_client = None
        self.re_registrations = 0
        self.rejected = False          # re-registration refused by service

    # -- lifecycle ------------------------------------------------------------
    def start(self) -> str:
        """Dial, register, start managers/workers. Returns the endpoint id
        the service assigned (blocks up to ``register_timeout``).

        ``on_connect`` is installed *before* the first dial: until the
        handshake assigns an endpoint id it is a guarded no-op, and from
        then on every re-dial — even one racing agent/manager startup —
        re-registers under that id. Installing it after start-up would
        leave a window where a drop re-dials without re-registering and
        the endpoint wedges (the service would just keep discarding the
        unregistered connection's heartbeats)."""
        if self.peer:
            # the peer server must listen before Register so the handshake
            # can advertise its address; a store is mandatory for serving
            from ..data import InMemoryKVStore
            from .peer import PeerServer
            store = self.agent_kw.get("store")
            if store is None:
                store = InMemoryKVStore()
                self.agent_kw["store"] = store
            self.peer_server = PeerServer("", store, host=self.peer_host)
        self.transport = TcpTransport(connect=self.address,
                                      on_connect=self._re_register)
        self.channel = Channel(transport=self.transport)
        self.endpoint_id = self._handshake()
        self.fns = WireFunctionClient(self.channel)
        # The client side of the peer plane is always on: even with the
        # server disabled (``peer=False``: nothing to advertise, nothing
        # listening) a consumer still needs PeerClient.fetch_raw so
        # cross-endpoint refs resolve via the hub relay — that IS the
        # fallback lane the benchmarks compare against.
        from .peer import PeerClient
        self.peer_client = PeerClient(self.endpoint_id)
        self.agent = EndpointAgent(
            self.endpoint_id, self.channel, self.fns.fetch,
            router=self.router, heartbeat_interval=self.heartbeat_interval,
            extra_handler=self._handle_extra,
            peer_server=self.peer_server, peer_client=self.peer_client,
            **self.agent_kw)
        for _ in range(self.n_managers):
            self.agent.add_manager(n_workers=self.workers_per_manager,
                                   **self.manager_kw)
        self.agent.start()
        return self.endpoint_id

    def stop(self) -> None:
        if self.agent is not None:
            self.agent.stop()          # closes peer server/client too
        elif self.peer_server is not None:
            self.peer_server.close()   # handshake never completed
        if self.channel is not None:
            self.channel.close()

    # -- handshake ------------------------------------------------------------
    def _register_msg(self, endpoint_id: str = "") -> dict:
        peer_addr = (self.peer_server.address
                     if self.peer_server is not None else "")
        return to_wire(Register(name=self.name, token=self._token,
                                endpoint_id=endpoint_id,
                                host=_socket.gethostname(), shm=self.shm,
                                peer_addr=peer_addr))

    def _handshake(self) -> str:
        """First registration: the agent recv loop is not running yet, so
        the ack is read straight off the channel."""
        deadline = time.time() + self.register_timeout
        while time.time() < deadline:
            if not self.channel.send_to_service(self._register_msg(),
                                                tag="register"):
                time.sleep(0.05)       # still dialing (backoff in transport)
                continue
            wire = self.channel.recv_at_endpoint(timeout=2.0)
            if wire is None:
                continue               # resend; duplicates are ignored
            env, _tag = wire
            try:
                msg = from_wire(env)
            except (ProtocolError, SerializationError):
                continue
            if isinstance(msg, RegisterAck):
                if not msg.ok:
                    raise RegistrationError(
                        f"registration refused: {msg.error}")
                self.endpoint_id = msg.endpoint_id
                self._apply_peer_secret(msg)
                self._maybe_attach_shm(msg)
                return msg.endpoint_id
        raise RegistrationError(
            f"no RegisterAck from {self.address} "
            f"within {self.register_timeout}s")

    # -- shared-memory fast path (DESIGN.md §7) -------------------------------
    def _maybe_attach_shm(self, ack: RegisterAck) -> None:
        """The RegisterAck carried a ring-pair offer: attach both segments,
        confirm over TCP, then switch the channel onto the
        :class:`ShmTransport`. Any failure sends a decline (so the service
        unlinks the pending rings) and stays on plain TCP — graceful
        fallback, never a wedge."""
        offer = ack.shm
        if not offer or self.channel is None:
            return
        decline = None
        if not self.shm or self.shm_attached \
                or isinstance(self.channel.transport, ShmTransport):
            decline = "shm declined"
        else:
            try:
                tx = ShmRing.attach(offer["e2s"])     # endpoint writes e2s
            except Exception as e:
                decline = f"{type(e).__name__}: {e}"
            else:
                try:
                    rx = ShmRing.attach(offer["s2e"])  # ...and reads s2e
                except Exception as e:
                    tx.close()
                    decline = f"{type(e).__name__}: {e}"
        if decline is not None:
            self.channel.send_to_service(to_wire(ShmAttach(
                endpoint_id=self.endpoint_id or "", ok=False,
                ring=offer.get("s2e", ""), error=decline)), tag="shm")
            return
        # confirm over TCP *before* switching: the service installs its
        # side when the confirm arrives, and because doorbells ride the
        # same TCP stream, every pre-switch frame sorts before the first
        # ring frame on both sides
        if not self.channel.send_to_service(to_wire(ShmAttach(
                endpoint_id=self.endpoint_id or "", ok=True,
                ring=offer["s2e"])), tag="shm"):
            tx.close()
            rx.close()
            return
        self.channel.transport = ShmTransport(self.transport, tx=tx, rx=rx)
        self.shm_attached = True

    def _teardown_shm(self) -> None:
        """Drop back to the raw TCP transport (connection loss: the rings
        die with the link — the service unlinked them when it saw the
        drop; in-ring frames are recovered by requeue-on-disconnect)."""
        ch = self.channel
        tr = ch.transport if ch is not None else None
        if isinstance(tr, ShmTransport):
            ch.transport = self.transport
            tr.release_rings()
        self.shm_attached = False

    def _re_register(self) -> None:
        """TcpTransport.on_connect: runs on the reader thread right after
        a successful re-dial."""
        if self.channel is None or self.endpoint_id is None:
            return
        self.re_registrations += 1
        self._teardown_shm()           # rings died with the old connection
        self.channel.reconnect()
        self.channel.send_to_service(self._register_msg(self.endpoint_id),
                                     tag="register")

    def _apply_peer_secret(self, ack: RegisterAck) -> None:
        """Arm the PeerServer with the id + secret the service assigned —
        from here on it can validate peer-tokens offline. The secret is
        stable across re-attach, so outstanding consumer grants survive a
        re-registration."""
        if self.peer_server is None or not ack.peer_secret:
            return
        self.peer_server.endpoint_id = ack.endpoint_id
        try:
            self.peer_server.set_secret(bytes.fromhex(ack.peer_secret))
        except ValueError:
            pass

    def _handle_extra(self, msg: Any) -> None:
        if isinstance(msg, FnResponse) and self.fns is not None:
            self.fns.handle_response(msg)
        elif isinstance(msg, RegisterAck):
            if msg.ok:
                # ack for a re-registration: a fresh ring offer may ride
                # it, and the peer secret is re-delivered
                self._apply_peer_secret(msg)
                self._maybe_attach_shm(msg)
            else:
                # Re-registration refused (e.g. a fully restarted service
                # no longer knows this endpoint id). Tasks already queued
                # keep executing; the flag tells operators a fresh `start`
                # (new registration, new id) is needed.
                self.rejected = True


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m repro.core.endpoint",
        description="Federated endpoint agent: connect to a FuncXService "
                    "TCP listener, register, and serve tasks with local "
                    "managers/workers (paper §4.3 deployed for real).")
    p.add_argument("--connect", required=True, metavar="HOST:PORT",
                   help="address of the service listener "
                        "(FuncXService.listen())")
    p.add_argument("--token", default="",
                   help="bearer token: Token.encode() JSON, or @FILE to "
                        "read it from a file")
    p.add_argument("--name", default="remote-endpoint")
    p.add_argument("--managers", type=int, default=1)
    p.add_argument("--workers", type=int, default=4,
                   help="workers per manager")
    p.add_argument("--router", default="warming_aware")
    p.add_argument("--heartbeat", type=float, default=0.05,
                   help="heartbeat interval, seconds")
    p.add_argument("--no-shm", action="store_true",
                   help="stay on TCP even when the service offers a "
                        "same-host shared-memory ring")
    p.add_argument("--no-peer", action="store_true",
                   help="disable the peer data plane: cross-endpoint "
                        "DataRefs resolve via the hub relay only")
    p.add_argument("--store", default="memory",
                   choices=["memory", "sharedfs", "device"],
                   help="local store kind (sharedfs uses a temp dir)")
    p.add_argument("--stage-limit", type=int, default=SERVICE_PAYLOAD_LIMIT,
                   help="stage-out threshold in bytes: results packing "
                        "larger than this become DataRefs into the local "
                        "store (default: the 10 MB service limit)")
    p.add_argument("--containers", default="", metavar="MODULE:FUNC",
                   help="container-spec installer: import MODULE and call "
                        "FUNC(registry) before serving — how subprocess "
                        "endpoints learn real ContainerSpecs (e.g. "
                        "repro.serve.fabric:install for the jit model zoo)")
    args = p.parse_args(argv)
    token = args.token
    if token.startswith("@"):
        with open(token[1:]) as f:
            token = f.read().strip()
    from ..data import make_store
    if args.store == "sharedfs":
        import tempfile
        store = make_store("sharedfs", root=tempfile.mkdtemp(
            prefix="repro-ep-store-"))
    else:
        store = make_store(args.store)
    registry = None
    if args.containers:
        import importlib
        mod_name, _, fn_name = args.containers.partition(":")
        installer = getattr(importlib.import_module(mod_name), fn_name)
        registry = ContainerRegistry()
        installer(registry)
    runner = RemoteEndpointRunner(
        args.connect, token, name=args.name, n_managers=args.managers,
        workers_per_manager=args.workers, router=args.router,
        heartbeat_interval=args.heartbeat, shm=not args.no_shm,
        peer=not args.no_peer, store=store, stage_limit=args.stage_limit,
        registry=registry)
    eid = runner.start()
    # parseable readiness line — parents wait on this before submitting
    # (field 2 is the endpoint id; the shm/peer markers tell benches which
    # planes actually engaged)
    peer_addr = (runner.peer_server.address
                 if runner.peer_server is not None else "0")
    print(f"ENDPOINT_READY {eid} shm={1 if runner.shm_attached else 0} "
          f"peer={peer_addr}", flush=True)
    try:
        while True:
            time.sleep(0.5)
    except KeyboardInterrupt:
        pass
    finally:
        runner.stop()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
