"""Hierarchical interchange (DESIGN.md §11): the paper's mid-tier relay.

funcX reached 130k+ concurrent workers and >100k queued tasks through an
*interchange* that sits between the cloud service and the workers,
queueing and fanning out tasks asynchronously (paper §5, fig. 4; the
same component anchors the earlier Serverless-Supercomputing prototype).
This module makes that tier real:

- **Upstream** the :class:`Interchange` is indistinguishable from one
  ordinary endpoint: it dials the service's TCP listener, performs the
  same ``Register``/``RegisterAck`` handshake, re-registers after
  connection cuts, and advertises one synthesized :class:`Heartbeat`
  whose load/warmth/build-cost fields aggregate the whole subtree — so
  federation routing sees "one big warm endpoint" and the service stays
  at O(1) threads no matter how many leaves hang below.
- **Downstream** it runs its own :class:`SocketReactor` + listener +
  :class:`ChannelHub` mini-forwarder speaking the *identical* wire
  protocol, so anything that can register with the service can register
  with an interchange — including another interchange (relay-of-relays
  nesting falls out for free).
- **Between** the two sides sits a deep task backlog (``depth``,
  default 150k specs) whose remaining room is advertised upstream as
  ``Heartbeat.credits`` — the backpressure signal the service-side
  forwarder respects — and drained by warmth-aware internal routing
  (the same ``make_router(tier="endpoint")`` machinery the service
  uses) under per-leaf outstanding-task windows.

Pack-once holds through the hop: task payloads arrive as opaque
``PackedBuffer`` frames and re-emit as borrowed segments — the relay
never deserializes or re-serializes a payload byte.

Exactly-once is preserved per tier with the PR 4/5 invariants:

- leaf death (missed heartbeats) or leaf removal requeues that leaf's
  in-flight specs into the central backlog for redispatch;
- an upstream cut parks outgoing result envelopes; the heartbeat loop
  retransmits them after the automatic re-dial + re-register, and the
  service's ``task.done`` check drops any duplicate that races a
  requeued re-execution.

Elasticity: the interchange exposes the ``pending_tasks`` /
``idle_workers`` / ``block_idle`` surface :class:`ElasticStrategy`
drives, and :class:`LeafProvider` turns provider blocks into whole leaf
endpoint *processes* dialing the downstream listener — backlog grows,
leaves spawn; backlog drains, leaves reap.
"""
from __future__ import annotations

import argparse
import collections
import hmac
import itertools
import signal
import threading
import time
from typing import Deque, Dict, List, Optional, Set, Tuple

from ..serialization import SerializationError
from .comms import (
    Channel,
    ChannelHub,
    SocketReactor,
    TcpListener,
    TcpTransport,
    parse_hostport,
)
from .endpoint import RemoteEndpointRunner, _BoundedSet, \
    spawn_endpoint_process
from .errors import RegistrationError
from .protocol import (
    Ack,
    FnRequest,
    FnResponse,
    Heartbeat,
    HubFetch,
    PeerData,
    ProtocolError,
    Register,
    RegisterAck,
    ResolvePeer,
    ResolvePeerAck,
    ResultBatch,
    ResultMsg,
    TaskBatch,
    TaskSpec,
    from_wire,
    to_wire,
    to_wire_parts,
)
from .provisioning import Provider
from .routing import EndpointInfo, RoutingContext, WarmthView, make_router
from .tasks import now


class LeafLine:
    """One downstream leaf's state inside the interchange — the mirror of
    the service-side ``EndpointLine``, except it holds the dispatched
    :class:`TaskSpec` objects themselves: the interchange has no
    TaskStore, so the specs must survive in the line for
    requeue-on-leaf-death."""

    def __init__(self, endpoint_id: str, channel: Channel,
                 lock: threading.RLock):
        self.endpoint_id = endpoint_id
        self.channel = channel
        self._lock = lock
        self.in_flight: Dict[str, TaskSpec] = {}
        self.advertised = Heartbeat(endpoint_id=endpoint_id)
        self.last_heartbeat = time.time()
        self.connected = True
        # tasks sent since the last heartbeat refreshed the leaf's credit
        # advertisement (only consulted when the leaf advertises credits,
        # i.e. is itself an interchange)
        self.sent_since_credit = 0
        self.dispatched = 0
        self.results = 0

    def in_flight_count(self) -> int:
        with self._lock:
            return len(self.in_flight)

    def info(self) -> EndpointInfo:
        """Snapshot for the interchange's internal endpoint-tier router."""
        adv = self.advertised
        warmth = WarmthView.from_heartbeat(adv)    # snapshot-local copy
        return EndpointInfo(
            endpoint_id=self.endpoint_id,
            connected=self.connected and self.channel.connected,
            service_queue=0,
            in_flight=self.in_flight_count(),
            queued=adv.queued,
            idle_workers=adv.idle_workers,
            capacity=adv.capacity,
            warm_idle=warmth.idle,
            warm_total=warmth.total,
        )

    def window(self, default_window: int, queue_factor: int) -> int:
        """How many more tasks this leaf may have outstanding.

        A leaf that advertises credits (a nested interchange) sets the
        window itself: its remaining credits minus what we sent since
        that advertisement. A plain leaf gets ``capacity ×
        queue_factor`` (or ``default_window`` before its first
        heartbeat) minus what is already in flight — deep enough to keep
        every worker busy through the RTT, shallow enough that the bulk
        of an absorbed burst stays in the central backlog where it can
        be rerouted when a leaf dies."""
        adv = self.advertised
        with self._lock:
            outstanding = len(self.in_flight)
            sent = self.sent_since_credit
        if adv.credits >= 0:
            return max(0, adv.credits - sent)
        budget = adv.capacity * queue_factor if adv.capacity > 0 \
            else default_window
        return max(0, budget - outstanding)


class Interchange:
    """A relay node: one endpoint upstream, a mini-service downstream.

    ``start()`` opens the downstream listener, dials ``address``,
    registers (same handshake as a remote endpoint), and starts the five
    relay threads: upstream recv, downstream dispatch, downstream recv
    (hub select over all leaves), heartbeat synthesis, and leaf
    liveness monitoring. Leaves connect to :attr:`leaf_address` with the
    ordinary endpoint CLI (``python -m repro.core.endpoint --connect``)
    — or another Interchange dials it for relay-of-relays nesting.
    """

    def __init__(self, address, token: str, *,
                 name: str = "interchange",
                 listen_host: str = "127.0.0.1", listen_port: int = 0,
                 depth: int = 150_000,
                 router: str = "warming_aware",
                 batch_size: int = 64,
                 heartbeat_interval: float = 0.05,
                 leaf_timeout: float = 0.5,
                 register_timeout: float = 30.0,
                 handshake_timeout: float = 5.0,
                 leaf_window: int = 32,
                 queue_factor: int = 4,
                 leaf_token: Optional[str] = None,
                 dedup_capacity: int = 262_144):
        self.address = (parse_hostport(address)
                        if isinstance(address, str) else address)
        self._token = token
        self.name = name
        self.listen_host = listen_host
        self.listen_port = listen_port
        self.depth = depth
        self.router = make_router(router, tier="endpoint")
        self.batch_size = batch_size
        self.heartbeat_interval = heartbeat_interval
        self.leaf_timeout = leaf_timeout
        self.register_timeout = register_timeout
        self.handshake_timeout = handshake_timeout
        self.leaf_window = leaf_window
        self.queue_factor = queue_factor
        # downstream registration credential: leaves present the same
        # token the interchange uses upstream unless told otherwise
        self.leaf_token = token if leaf_token is None else leaf_token

        # upstream side
        self.endpoint_id: Optional[str] = None
        self.channel: Optional[Channel] = None
        self.transport: Optional[TcpTransport] = None
        self.re_registrations = 0
        self.rejected = False

        # downstream side
        self._reactor: Optional[SocketReactor] = None
        self._listener: Optional[TcpListener] = None
        self._hub = ChannelHub()
        self._leaves: Dict[str, LeafLine] = {}
        self._leaf_counter = itertools.count()
        self._leaf_procs: Dict[str, object] = {}   # LeafProvider children

        # the deep bounded backlog between the two sides
        self._lock = threading.RLock()
        self._cond = threading.Condition(self._lock)
        self._backlog: Deque[TaskSpec] = collections.deque()
        self._known: Set[str] = set()       # queued or in flight downstream
        self._completed = _BoundedSet(dedup_capacity)
        self._unsent: Deque[List[ResultMsg]] = collections.deque()
        self._unsent_lock = threading.Lock()

        # function-body cache: leaves pull FnRequest from us; we pull
        # from upstream once per function and fan the body out
        self._fn_lock = threading.Lock()
        self._fn_cache: Dict[str, FnResponse] = {}
        self._fn_waiters: Dict[str, Set[str]] = {}

        # subtree build-cost aggregation (EWMA per warmth key)
        self._costs_lock = threading.Lock()
        self._build_costs: Dict[str, float] = {}

        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self.strategy = None                # ElasticStrategy, if driven

        # metrics
        self.tasks_received = 0
        self.tasks_dispatched = 0
        self.results_forwarded = 0
        self.requeues = 0
        self.dedup_dropped = 0
        self.backlog_peak = 0

    # ------------------------------------------------------------- lifecycle
    @property
    def leaf_address(self) -> str:
        """``host:port`` leaves (or nested interchanges) dial into."""
        host, port = self._listener.address
        return f"{host}:{port}"

    def start(self) -> str:
        """Listen downstream, register upstream, start the relay loops.
        Returns the endpoint id the upstream tier assigned."""
        self._reactor = SocketReactor()
        self._listener = TcpListener(self.listen_host, self.listen_port,
                                     self._handle_leaf_connection,
                                     reactor=self._reactor)
        # on_connect installed before the first dial: every re-dial —
        # including one racing startup — re-registers under the assigned
        # id (same invariant as RemoteEndpointRunner)
        self.transport = TcpTransport(connect=self.address,
                                      on_connect=self._re_register)
        self.channel = Channel(transport=self.transport)
        self.endpoint_id = self._handshake()
        for tname, fn in [("up-recv", self._upstream_loop),
                          ("dispatch", self._dispatch_loop),
                          ("leaf-recv", self._leaf_recv_loop),
                          ("hb", self._heartbeat_loop),
                          ("monitor", self._monitor_loop)]:
            t = threading.Thread(target=fn, daemon=True,
                                 name=f"ix-{self.name}-{tname}")
            t.start()
            self._threads.append(t)
        return self.endpoint_id

    def stop(self) -> None:
        self._stop.set()
        with self._cond:
            self._cond.notify_all()
        if self.strategy is not None:
            self.strategy.stop()
        for proc in list(self._leaf_procs.values()):
            try:
                proc.terminate()
            except Exception:
                pass
        self._leaf_procs.clear()
        with self._lock:
            lines = list(self._leaves.values())
            self._leaves.clear()
        for line in lines:
            self._hub.unregister(line.endpoint_id)
            line.channel.close()
        if self._listener is not None:
            self._listener.close()
        if self._reactor is not None:
            self._reactor.close()
        if self.channel is not None:
            self.channel.close()

    # ------------------------------------------------------ upstream handshake
    def _register_msg(self, endpoint_id: str = "") -> dict:
        return to_wire(Register(name=self.name, token=self._token,
                                endpoint_id=endpoint_id))

    def _handshake(self) -> str:
        """First registration: the upstream recv loop is not running yet,
        so the ack is read straight off the channel (duplicate acks from
        resent Registers are ignored)."""
        deadline = time.time() + self.register_timeout
        while time.time() < deadline:
            if not self.channel.send_to_service(self._register_msg(),
                                                tag="register"):
                time.sleep(0.05)       # still dialing (transport backoff)
                continue
            wire = self.channel.recv_at_endpoint(timeout=2.0)
            if wire is None:
                continue
            env, _tag = wire
            try:
                msg = from_wire(env)
            except (ProtocolError, SerializationError):
                continue
            if isinstance(msg, RegisterAck):
                if not msg.ok:
                    raise RegistrationError(
                        f"interchange registration refused: {msg.error}")
                self.endpoint_id = msg.endpoint_id
                return msg.endpoint_id
        raise RegistrationError(
            f"no RegisterAck from {self.address} "
            f"within {self.register_timeout}s")

    def _re_register(self) -> None:
        """TcpTransport.on_connect — re-attach under the assigned id after
        any upstream cut. The service requeues what it had in flight; our
        ``_known`` intake dedup absorbs the re-dispatch of anything still
        held here, and parked result envelopes flush on the next beat."""
        if self.channel is None or self.endpoint_id is None:
            return
        self.re_registrations += 1
        self.channel.reconnect()
        self.channel.send_to_service(self._register_msg(self.endpoint_id),
                                     tag="register")

    # ----------------------------------------------------- downstream accept
    def _handle_leaf_connection(self, transport: TcpTransport,
                                peer: Tuple[str, int]) -> None:
        """Per-leaf handshake (own thread, spawned by the listener) — the
        same protocol the service speaks, so plain endpoints and nested
        interchanges register identically."""
        channel = Channel(transport=transport)
        msg = None
        deadline = time.time() + self.handshake_timeout
        while time.time() < deadline and not self._stop.is_set():
            wire = channel.recv_at_service(timeout=0.25)
            if wire is None:
                continue
            env, _tag = wire
            try:
                m = from_wire(env)
            except (ProtocolError, SerializationError):
                continue
            if isinstance(m, Register):
                msg = m
                break
        if msg is None:
            channel.close()
            return
        if self.leaf_token and not hmac.compare_digest(msg.token,
                                                       self.leaf_token):
            channel.send_to_endpoint(to_wire(RegisterAck(
                ok=False, error="interchange: leaf token mismatch")),
                tag="register")
            channel.close()
            return
        if msg.endpoint_id:            # reattach after a connection loss
            with self._lock:
                line = self._leaves.get(msg.endpoint_id)
            if line is None:
                channel.send_to_endpoint(to_wire(RegisterAck(
                    ok=False, error=f"unknown leaf {msg.endpoint_id}")),
                    tag="register")
                channel.close()
                return
            eid = msg.endpoint_id
            self._reattach_leaf(line, channel)
        else:
            eid = f"{self.name}/leaf{next(self._leaf_counter)}"
            line = LeafLine(eid, channel, self._lock)
            with self._lock:
                self._leaves[eid] = line
            self._hub.register(eid, channel)
        channel.send_to_endpoint(
            to_wire(RegisterAck(ok=True, endpoint_id=eid)), tag="register")
        with self._cond:
            self._cond.notify()

    def _reattach_leaf(self, line: LeafLine, channel: Channel) -> None:
        with self._lock:
            old = line.channel
            line.channel = channel
            line.connected = True
            line.last_heartbeat = time.time()
        self._hub.unregister(line.endpoint_id)
        self._hub.register(line.endpoint_id, channel)
        if old is not channel:
            old.close()
        self.requeue_in_flight(line)

    def remove_leaf(self, endpoint_id: str) -> None:
        """Reap one leaf (provider scale-in, or operator action): its
        in-flight specs go back into the backlog for redispatch."""
        with self._lock:
            line = self._leaves.pop(endpoint_id, None)
        if line is None:
            return
        self._hub.unregister(endpoint_id)
        self.requeue_in_flight(line)
        line.channel.close()

    def leaf_lines(self) -> List[LeafLine]:
        with self._lock:
            return list(self._leaves.values())

    def leaf_infos(self) -> List[EndpointInfo]:
        return [ln.info() for ln in self.leaf_lines()]

    # ------------------------------------------------------- upstream intake
    def _upstream_loop(self) -> None:
        while not self._stop.is_set():
            wire = self.channel.recv_at_endpoint(timeout=0.05)
            if wire is None:
                continue
            env, _tag = wire
            try:
                msg = from_wire(env)
            except (ProtocolError, SerializationError):
                continue               # poison frame: drop, keep the loop
            if isinstance(msg, TaskBatch):
                self._absorb(msg.tasks)
            elif isinstance(msg, FnResponse):
                self._handle_fn_response(msg)
            elif isinstance(msg, RegisterAck):
                if not msg.ok:
                    self.rejected = True

    def _absorb(self, specs: List[TaskSpec]) -> None:
        """Take one upstream TaskBatch into the backlog. Payloads stay
        packed (opaque ``PackedBuffer`` frames) — this is the queueing
        hop, not a serialization hop. Duplicates of tasks still held
        here (the service requeued in-flight work across a reconnect)
        are dropped; anything already completed re-executes downstream
        and the upstream ``task.done`` check drops the extra result."""
        if not specs:
            return
        t_recv = now()
        fresh = []
        with self._cond:
            for s in specs:
                if s.task_id in self._known:
                    self.dedup_dropped += 1
                    continue
                self._known.add(s.task_id)
                fresh.append(s)
            self._backlog.extend(fresh)
            depth = len(self._backlog)
            if depth > self.backlog_peak:
                self.backlog_peak = depth
            if fresh:
                self._cond.notify()
        self.tasks_received += len(fresh)
        self.channel.send_to_service(
            to_wire(Ack(task_ids=[s.task_id for s in specs],
                        t_endpoint_recv=t_recv)), tag="ack")

    # ---------------------------------------------------- downstream dispatch
    def _pop_run(self, limit: int) -> List[TaskSpec]:
        """Pop up to ``limit`` consecutive backlog specs sharing one
        (warmth_key, container_type) — a run routes as one packed
        TaskBatch to one leaf. Caller must hold the lock."""
        q = self._backlog
        specs: List[TaskSpec] = []
        if not q:
            return specs
        key = (q[0].warmth_key, q[0].container_type)
        while q and len(specs) < limit and \
                (q[0].warmth_key, q[0].container_type) == key:
            specs.append(q.popleft())
        return specs

    def _requeue_front(self, specs: List[TaskSpec]) -> None:
        """Caller must hold the lock."""
        self._backlog.extendleft(reversed(specs))

    def _eligible_lines(self) -> List[LeafLine]:
        return [ln for ln in self.leaf_lines()
                if ln.connected and ln.channel.connected
                and ln.window(self.leaf_window, self.queue_factor) > 0]

    def _dispatch_loop(self) -> None:
        while not self._stop.is_set():
            with self._cond:
                if not self._backlog:
                    self._cond.wait(timeout=0.05)
                    continue
            lines = self._eligible_lines()
            if not lines:
                # backlog but nowhere to send (leaves full/absent): the
                # backlog is the buffer — that's its job
                time.sleep(0.005)
                continue
            self._dispatch_round(lines)

    def _dispatch_round(self, lines: List[LeafLine]) -> None:
        """Drain up to one window's worth of backlog across ``lines``:
        route each key-run with the endpoint-tier policy over the leaf
        snapshots, feeding picks back so a round spreads instead of
        dog-piling the momentary best leaf."""
        by_id = {ln.endpoint_id: ln for ln in lines}
        infos = [ln.info() for ln in lines]
        windows = {ln.endpoint_id:
                   ln.window(self.leaf_window, self.queue_factor)
                   for ln in lines}
        budget = sum(windows.values())
        while budget > 0 and not self._stop.is_set():
            with self._cond:
                specs = self._pop_run(min(self.batch_size, budget))
            if not specs:
                return
            head = specs[0]
            ctx = RoutingContext(warmth_key=head.warmth_key or None,
                                 container_type=head.container_type)
            pool = [i for i in infos if windows[i.endpoint_id] > 0]
            eid = self.router.select_ctx(ctx, pool)
            if eid is None:
                with self._cond:
                    self._requeue_front(specs)
                return
            room = windows[eid]
            if len(specs) > room:
                with self._cond:
                    self._requeue_front(specs[room:])
                specs = specs[:room]
            if self._send_batch(by_id[eid], specs):
                windows[eid] -= len(specs)
                budget -= len(specs)
                for inf in infos:
                    if inf.endpoint_id == eid:
                        for _ in specs:
                            inf.note_pick(ctx)
                        break
            else:
                with self._cond:
                    self._requeue_front(specs)
                return

    def _send_batch(self, line: LeafLine, specs: List[TaskSpec]) -> bool:
        # Record in-flight BEFORE the send: a fast leaf can execute a
        # noop and return its result before this thread re-acquires the
        # lock, and a result that finds no in-flight entry would leak
        # one unit of the leaf's dispatch window forever (enough leaks
        # freeze dispatch with work still in the backlog).
        with self._lock:
            for s in specs:
                line.in_flight[s.task_id] = s
        # scatter-gather re-emit: the packed payload buffers ride behind
        # the envelope as borrowed views — byte-identical through the hop
        env, segs = to_wire_parts(TaskBatch(tasks=specs))
        if not line.channel.send_parts_to_endpoint(env, segs, tag="tasks"):
            with self._lock:
                for s in specs:
                    line.in_flight.pop(s.task_id, None)
            return False
        with self._lock:
            line.sent_since_credit += len(specs)
            line.dispatched += len(specs)
        self.tasks_dispatched += len(specs)
        return True

    # --------------------------------------------------------- downstream recv
    def _leaf_recv_loop(self) -> None:
        while not self._stop.is_set():
            for eid, buf in self._hub.poll(timeout=0.05):
                with self._lock:
                    line = self._leaves.get(eid)
                if line is None:
                    continue
                try:
                    msg = from_wire(buf.unpack())
                except (ProtocolError, SerializationError):
                    continue
                if isinstance(msg, Heartbeat):
                    self._leaf_heartbeat(line, msg)
                elif isinstance(msg, Ack):
                    pass               # receipt only; specs stay in flight
                elif isinstance(msg, ResultBatch):
                    self._leaf_results(line, msg)
                elif isinstance(msg, ResultMsg):
                    self._leaf_results(line, ResultBatch(results=[msg]))
                elif isinstance(msg, FnRequest):
                    self._leaf_fn_request(line, msg)
                elif isinstance(msg, ResolvePeer):
                    line.channel.send_to_endpoint(to_wire(ResolvePeerAck(
                        req_id=msg.req_id, endpoint_id=msg.endpoint_id,
                        ok=False, error="interchange: no peer signaling")),
                        tag="peer")
                elif isinstance(msg, HubFetch):
                    line.channel.send_to_endpoint(to_wire(PeerData(
                        req_id=msg.req_id, key=msg.key, ok=False,
                        error="interchange: no hub relay")), tag="peer")

    def _leaf_heartbeat(self, line: LeafLine, hb: Heartbeat) -> None:
        line.last_heartbeat = time.time()
        line.advertised = hb
        with self._lock:
            line.sent_since_credit = 0     # credit window refreshed
        if hb.build_costs:
            with self._costs_lock:
                for k, v in hb.build_costs.items():
                    prev = self._build_costs.get(k)
                    self._build_costs[k] = (v if prev is None
                                            else 0.8 * prev + 0.2 * v)
        if not line.connected:
            line.connected = True          # leaf came back
            with self._cond:
                self._cond.notify()

    def _leaf_results(self, line: LeafLine, batch: ResultBatch) -> None:
        if not batch.results:
            return
        fresh: List[ResultMsg] = []
        with self._cond:
            for res in batch.results:
                line.in_flight.pop(res.task_id, None)
                if not self._completed.add(res.task_id):
                    continue       # duplicate (requeue raced a result)
                self._known.discard(res.task_id)
                fresh.append(res)
        if not fresh:
            return
        line.results += len(fresh)
        self.results_forwarded += len(fresh)
        self._forward_results(fresh)

    def _forward_results(self, results: List[ResultMsg]) -> None:
        """Re-emit one ResultBatch upstream, packed results as borrowed
        segments. A refused send (upstream cut) parks the member results
        for batch-wise retransmission by the heartbeat loop — without
        the parking, a result produced during an outage would be lost
        forever (the task is in ``_completed``, so the re-execution the
        service requeues would be dropped as a duplicate here)."""
        env, segs = to_wire_parts(ResultBatch(results=results))
        if not self.channel.send_parts_to_service(env, segs, tag="results"):
            with self._unsent_lock:
                self._unsent.append(results)

    def flush_unsent(self) -> None:
        while True:
            with self._unsent_lock:
                if not self._unsent:
                    return
                results = self._unsent[0]
            env, segs = to_wire_parts(ResultBatch(results=results))
            if not self.channel.send_parts_to_service(env, segs,
                                                      tag="results"):
                return
            with self._unsent_lock:
                self._unsent.popleft()

    # ------------------------------------------------------- function plane
    def _leaf_fn_request(self, line: LeafLine, req: FnRequest) -> None:
        """Leaves pull function bodies from us exactly like they would
        from the service; we pull each body upstream once and serve the
        whole subtree from cache (the leaf's fetch re-sends about once a
        second, so an upstream frame lost to a cut is re-pulled)."""
        fid = req.function_id
        with self._fn_lock:
            resp = self._fn_cache.get(fid)
            if resp is None:
                self._fn_waiters.setdefault(fid, set()).add(line.endpoint_id)
        if resp is not None:
            line.channel.send_to_endpoint(to_wire(resp), tag="fn")
            return
        self.channel.send_to_service(to_wire(FnRequest(function_id=fid)),
                                     tag="fn")

    def _handle_fn_response(self, resp: FnResponse) -> None:
        with self._fn_lock:
            if not resp.error:
                self._fn_cache[resp.function_id] = resp
            waiters = self._fn_waiters.pop(resp.function_id, set())
        for eid in waiters:
            with self._lock:
                line = self._leaves.get(eid)
            if line is not None:
                line.channel.send_to_endpoint(to_wire(resp), tag="fn")

    # ------------------------------------------------- heartbeat + liveness
    def _heartbeat_loop(self) -> None:
        while not self._stop.is_set():
            self.flush_unsent()
            self.channel.send_to_service(to_wire(self._heartbeat()),
                                         tag="hb")
            time.sleep(self.heartbeat_interval)

    def _heartbeat(self) -> Heartbeat:
        """Synthesize the subtree as one endpoint: aggregate load, merged
        warm dicts, aggregated build costs — plus the backpressure fields
        (``credits`` = remaining backlog room) the upstream forwarder
        caps its dispatch against."""
        lines = self.leaf_lines()
        views = []
        queued_down = idle = cap = 0
        with self._lock:
            in_flight = sum(len(ln.in_flight) for ln in lines)
        for ln in lines:
            adv = ln.advertised
            views.append(WarmthView.from_heartbeat(adv))
            queued_down += adv.queued
            idle += adv.idle_workers
            cap += adv.capacity
        merged = WarmthView.merge(views)
        with self._cond:
            backlog = len(self._backlog)
        with self._costs_lock:
            costs = dict(self._build_costs)
        held = backlog + in_flight
        return Heartbeat(endpoint_id=self.endpoint_id or "",
                         ts=time.time(),
                         queued=held + queued_down,
                         idle_workers=idle, capacity=cap,
                         warm_idle=merged.idle, warm_total=merged.total,
                         build_costs=costs,
                         credits=max(0, self.depth - held),
                         backlog=backlog, depth=self.depth)

    def _monitor_loop(self) -> None:
        """Leaf liveness (the per-tier half of requeue-on-disconnect): a
        leaf that misses heartbeats gets its in-flight specs back into
        the central backlog for redispatch to surviving leaves."""
        while not self._stop.is_set():
            time.sleep(self.leaf_timeout / 4)
            cutoff = time.time() - self.leaf_timeout
            for line in self.leaf_lines():
                if line.connected and line.last_heartbeat < cutoff:
                    line.connected = False
                    self.requeue_in_flight(line)

    def requeue_in_flight(self, line: LeafLine) -> None:
        with self._cond:
            specs = [s for s in line.in_flight.values()
                     if s.task_id not in self._completed]
            line.in_flight.clear()
            self._requeue_front(specs)
            self.requeues += len(specs)
            if specs:
                self._cond.notify()

    # ------------------------------------------- ElasticStrategy surface
    def pending_tasks(self) -> int:
        """Queued backlog depth + downstream in-flight — what the
        strategy's backlog_per_block sizing consumes."""
        with self._cond:
            backlog = len(self._backlog)
        with self._lock:
            in_flight = sum(len(ln.in_flight)
                            for ln in self._leaves.values())
        return backlog + in_flight

    def idle_workers(self) -> int:
        return sum(ln.advertised.idle_workers for ln in self.leaf_lines())

    def block_idle(self, leaf_ids: List[str]) -> bool:
        """A provider block (one or more whole leaves) is reapable when
        every member leaf is drained and fully idle. Missing leaves
        (already reaped) don't block the decision."""
        for eid in leaf_ids:
            with self._lock:
                line = self._leaves.get(eid)
            if line is None:
                continue
            adv = line.advertised
            if line.in_flight_count() or adv.queued:
                return False
            if adv.capacity and adv.idle_workers < adv.capacity:
                return False
        return True


# ---------------------------------------------------------------------------
# Providers whose blocks are whole leaves (ElasticStrategy drives these
# against an Interchange instead of a manager-growing EndpointAgent)
# ---------------------------------------------------------------------------

class LeafProvider(Provider):
    """Each block is ``nodes_per_block`` leaf endpoint *subprocesses*
    dialing the interchange's downstream listener — elastic scale-out
    spawns real processes, scale-in terminates them (their in-flight
    work requeues into the backlog)."""

    name = "leaf"

    def __init__(self, interchange: Interchange, *,
                 managers_per_leaf: int = 1, acquire_delay: float = 0.0,
                 spawn_kw: Optional[dict] = None, **kw):
        super().__init__(**kw)
        self.ix = interchange
        self.managers_per_leaf = managers_per_leaf
        self.acquire_delay = acquire_delay
        self.spawn_kw = spawn_kw or {}

    def acquisition_delay(self) -> float:
        return self.acquire_delay

    def start_block(self, endpoint) -> list:
        delay = self.acquisition_delay()
        if delay > 0:
            time.sleep(delay)
        ids = []
        for _ in range(self.nodes_per_block):
            proc, eid = spawn_endpoint_process(
                self.ix.leaf_address, self.ix.leaf_token,
                name=f"{self.ix.name}-leaf",
                n_managers=self.managers_per_leaf,
                workers=self.workers_per_node,
                shm=False, peer=False, **self.spawn_kw)
            self.ix._leaf_procs[eid] = proc
            ids.append(eid)
        return ids

    def stop_block(self, endpoint, leaf_ids: list) -> None:
        for eid in leaf_ids:
            proc = self.ix._leaf_procs.pop(eid, None)
            self.ix.remove_leaf(eid)
            if proc is not None:
                try:
                    proc.terminate()
                except Exception:
                    pass


class ThreadLeafProvider(Provider):
    """In-process variant (tests, examples): each leaf is a full
    :class:`RemoteEndpointRunner` speaking the real wire protocol over
    loopback from threads in this process."""

    name = "leaf-threads"

    def __init__(self, interchange: Interchange, *,
                 managers_per_leaf: int = 1, acquire_delay: float = 0.0,
                 runner_kw: Optional[dict] = None, **kw):
        super().__init__(**kw)
        self.ix = interchange
        self.managers_per_leaf = managers_per_leaf
        self.acquire_delay = acquire_delay
        self.runner_kw = runner_kw or {}
        self._runners: Dict[str, RemoteEndpointRunner] = {}

    def acquisition_delay(self) -> float:
        return self.acquire_delay

    def start_block(self, endpoint) -> list:
        delay = self.acquisition_delay()
        if delay > 0:
            time.sleep(delay)
        ids = []
        for _ in range(self.nodes_per_block):
            runner = RemoteEndpointRunner(
                self.ix.leaf_address, self.ix.leaf_token,
                name=f"{self.ix.name}-leaf",
                n_managers=self.managers_per_leaf,
                workers_per_manager=self.workers_per_node,
                shm=False, peer=False, **self.runner_kw)
            eid = runner.start()
            self._runners[eid] = runner
            ids.append(eid)
        return ids

    def stop_block(self, endpoint, leaf_ids: list) -> None:
        for eid in leaf_ids:
            runner = self._runners.pop(eid, None)
            self.ix.remove_leaf(eid)
            if runner is not None:
                runner.stop()

    def stop_all(self) -> None:
        for eid in list(self._runners):
            self.stop_block(None, [eid])


def spawn_interchange_process(address, token: str, *,
                              name: str = "relay",
                              depth: int = 150_000,
                              min_blocks: int = 0, max_blocks: int = 4,
                              backlog_per_block: int = 0,
                              idle_timeout: float = 2.0,
                              leaf_workers: int = 4,
                              leaf_managers: int = 1,
                              acquire_delay: float = 0.0,
                              extra_args: Optional[list] = None,
                              stderr=None):
    """Spawn ``python -m repro.core.interchange`` as a child process and
    block until its readiness line. Returns
    ``(proc, endpoint_id, leaf_address)`` — dial ``leaf_address`` to hang
    endpoints (or more interchanges) below it."""
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
    capture = tempfile.TemporaryFile("w+") if stderr is None else None
    argv = [sys.executable, "-m", "repro.core.interchange",
            "--connect", address, "--token", token, "--name", name,
            "--depth", str(depth),
            "--min-blocks", str(min_blocks),
            "--max-blocks", str(max_blocks),
            "--backlog-per-block", str(backlog_per_block),
            "--idle-timeout", str(idle_timeout),
            "--leaf-workers", str(leaf_workers),
            "--leaf-managers", str(leaf_managers),
            "--acquire-delay", str(acquire_delay)]
    argv += extra_args or []
    proc = subprocess.Popen(
        argv, env=env, stdout=subprocess.PIPE,
        stderr=capture if capture is not None else stderr, text=True)
    line = (proc.stdout.readline() or "").strip()
    if not line.startswith("INTERCHANGE_READY"):
        proc.terminate()
        err = ""
        if capture is not None:
            proc.wait(timeout=5)
            capture.seek(0)
            err = capture.read()
        raise RuntimeError(
            f"interchange subprocess failed (got {line!r}): {err[-2000:]}")
    if capture is not None:
        capture.close()
    fields = line.split()
    leaf_addr = fields[2].partition("=")[2] if len(fields) > 2 else ""
    return proc, fields[1], leaf_addr


def main(argv: Optional[List[str]] = None) -> int:
    from .provisioning import ElasticStrategy
    p = argparse.ArgumentParser(
        prog="python -m repro.core.interchange",
        description="Hierarchical interchange: register upstream as one "
                    "endpoint, fan out downstream to elastic leaf "
                    "endpoint processes over the same wire protocol "
                    "(DESIGN.md §11).")
    p.add_argument("--connect", required=True, metavar="HOST:PORT",
                   help="upstream listener (a FuncXService — or another "
                        "interchange's leaf address, for nesting)")
    p.add_argument("--token", default="",
                   help="bearer token: raw string, or @FILE")
    p.add_argument("--name", default="interchange")
    p.add_argument("--listen-host", default="127.0.0.1")
    p.add_argument("--listen-port", type=int, default=0)
    p.add_argument("--depth", type=int, default=150_000,
                   help="backlog capacity advertised as heartbeat credits")
    p.add_argument("--router", default="warming_aware")
    p.add_argument("--batch", type=int, default=64)
    p.add_argument("--heartbeat", type=float, default=0.05)
    p.add_argument("--leaf-timeout", type=float, default=0.5)
    p.add_argument("--min-blocks", type=int, default=0)
    p.add_argument("--max-blocks", type=int, default=4)
    p.add_argument("--backlog-per-block", type=int, default=0,
                   help="tasks one leaf block absorbs (ElasticStrategy "
                        "backlog-depth sizing; 0 = pending-vs-idle)")
    p.add_argument("--idle-timeout", type=float, default=2.0)
    p.add_argument("--strategy-interval", type=float, default=0.05)
    p.add_argument("--leaf-workers", type=int, default=4)
    p.add_argument("--leaf-managers", type=int, default=1)
    p.add_argument("--acquire-delay", type=float, default=0.0,
                   help="simulated scheduler/cloud acquisition delay per "
                        "leaf block")
    args = p.parse_args(argv)
    token = args.token
    if token.startswith("@"):
        with open(token[1:]) as f:
            token = f.read().strip()
    ix = Interchange(args.connect, token, name=args.name,
                     listen_host=args.listen_host,
                     listen_port=args.listen_port,
                     depth=args.depth, router=args.router,
                     batch_size=args.batch,
                     heartbeat_interval=args.heartbeat,
                     leaf_timeout=args.leaf_timeout)
    eid = ix.start()
    provider = LeafProvider(ix, workers_per_node=args.leaf_workers,
                            managers_per_leaf=args.leaf_managers,
                            acquire_delay=args.acquire_delay)
    strategy = ElasticStrategy(ix, provider,
                               min_blocks=args.min_blocks,
                               max_blocks=args.max_blocks,
                               backlog_per_block=args.backlog_per_block,
                               idle_timeout=args.idle_timeout,
                               interval=args.strategy_interval)
    ix.strategy = strategy
    strategy.start()
    # parseable readiness line — parents wait on this before submitting
    print(f"INTERCHANGE_READY {eid} leaf={ix.leaf_address}", flush=True)
    # SIGTERM (what a supervising parent's .terminate() sends) must run
    # the same shutdown as Ctrl-C: ix.stop() reaps the elastic leaf
    # subprocesses, which would otherwise outlive the relay as orphans.
    def _terminate(signum, frame):
        raise KeyboardInterrupt
    signal.signal(signal.SIGTERM, _terminate)
    try:
        while True:
            time.sleep(0.5)
    except KeyboardInterrupt:
        pass
    finally:
        strategy.stop()
        ix.stop()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
