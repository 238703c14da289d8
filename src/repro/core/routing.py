"""Function routing (paper §6.2) behind one warmth-key mechanism.

The funcX agent routes each task to a manager:

  1. prefer managers with a *warm* copy of the expensive artifact the
     task needs — the paper's warm container, or (DESIGN.md §10) a
     jit-compiled executable — choosing the one with the most available
     warm workers (load balance);
  2. otherwise pick a manager at random (the paper's fallback and the
     baseline we benchmark against).

What "warm" means is named by a **warmth key**: by default the task's
container type, but any string advertised through the same
``warm_idle``/``warm_total`` heartbeat dicts (e.g.
``jit/<arch>/<step>/<bucket>`` for a compiled serving step). Every
placement decision flows through one :class:`RoutingContext` — container
warmth and jit warmth are two instances of the same mechanism — and all
advertised warm state is read and mutated through one
:class:`WarmthView` accessor.

Beyond-paper routers:
  - ``CostAwareRouter`` scores managers by expected completion time
    (queue wait + cold-start cost when no warm copy), using the
    endpoint's measured build times.
  - ``LocalityAwareRouter`` breaks warm ties toward managers whose local
    store already holds the task's input refs.

All routers consume the same advertised ``ManagerInfo`` snapshots, so
policies are swappable per endpoint (paper: 'modular scheduling
interfaces'). The federation tier (``EndpointRouter``) applies the same
policies one level up, over ``EndpointInfo`` snapshots.

Every entry point takes a :class:`RoutingContext` — the PR 9 legacy
positional-string shims (``RoutingContext.coerce``, string ``route``/
``select``, ``make_endpoint_router``) are gone.
"""
from __future__ import annotations

import random
import threading
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Mapping, Optional, Sequence, Tuple


# ---------------------------------------------------------------------------
# RoutingContext — the one argument every routing decision takes
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class RoutingContext:
    """Everything a router may consider for one placement decision.

    ``warmth_key`` names the expensive, reusable artifact the task wants
    to land next to: a warm container type, a jit-compiled executable
    (``jit/<arch>/<step>/<bucket>``), anything a worker advertises
    through the warm dicts. Unset, it defaults to ``container_type`` —
    the paper's original behaviour. When an explicit warmth key
    *refines* the container type, the container type remains a fallback
    warmth key: jit-warm beats container-warm beats cold.

    ``hints`` is an open side channel (policy knobs, tenant tags) that
    concrete routers may consult; core routers ignore unknown hints.
    """
    warmth_key: Optional[str] = None
    container_type: str = "python"
    input_keys: frozenset = frozenset()
    hints: Mapping[str, object] = field(default_factory=dict)

    @property
    def key(self) -> str:
        """Primary warmth key: the explicit one, else the container type."""
        return self.warmth_key or self.container_type

    @property
    def warmth_keys(self) -> Tuple[str, ...]:
        """Warmth keys in preference order (primary, then the container
        type when an explicit warmth key refines it)."""
        if self.warmth_key and self.warmth_key != self.container_type:
            return (self.warmth_key, self.container_type)
        return (self.key,)


# ---------------------------------------------------------------------------
# WarmthView — the one parsing point for advertised warm state
# ---------------------------------------------------------------------------

class WarmthView:
    """Accessor over the advertised warm dicts (``{warmth_key: count}``).

    Three layers used to parse these shapes independently — the manager's
    worker scan, the endpoint agent's heartbeat merge, and the service's
    ``EndpointInfo`` snapshot (plus every router's reads). They all go
    through this view now, so a change to what a warmth key *is* (jit
    keys riding next to container types, DESIGN.md §10) lands in one
    place. The view wraps the owning snapshot's dicts — mutations
    (``note_pick``) write through.
    """

    __slots__ = ("idle", "total")

    def __init__(self, idle: Optional[Dict[str, int]] = None,
                 total: Optional[Dict[str, int]] = None):
        self.idle = idle if idle is not None else {}
        self.total = total if total is not None else {}

    # -- queries -------------------------------------------------------------
    def warm_idle(self, key: str) -> int:
        return self.idle.get(key, 0)

    def warm_total(self, key: str) -> int:
        return self.total.get(key, 0)

    def is_warm(self, ctx: "RoutingContext") -> bool:
        return any(self.warm_total(k) > 0 for k in ctx.warmth_keys)

    # -- mutation ------------------------------------------------------------
    def note_pick(self, key: str) -> None:
        """Feed one routing pick back: an idle warm worker for ``key`` is
        about to become busy."""
        if self.idle.get(key, 0) > 0:
            self.idle[key] -= 1

    def add(self, key: str, *, idle: int = 0, total: int = 0) -> None:
        if idle:
            self.idle[key] = self.idle.get(key, 0) + idle
        if total:
            self.total[key] = self.total.get(key, 0) + total

    # -- builders (the three call sites) --------------------------------------
    @classmethod
    def tally(cls, workers: Iterable[Tuple[Iterable[str], bool]]
              ) -> "WarmthView":
        """Manager tier: fold ``(warm_keys, is_idle)`` per worker into one
        advertisement."""
        view = cls()
        for keys, is_idle in workers:
            for k in keys:
                view.add(k, idle=1 if is_idle else 0, total=1)
        return view

    @classmethod
    def merge(cls, views: Iterable["WarmthView"]) -> "WarmthView":
        """Endpoint tier: sum per-manager advertisements into the
        heartbeat's fleet-wide dicts."""
        out = cls()
        for v in views:
            for k, n in v.idle.items():
                out.idle[k] = out.idle.get(k, 0) + n
            for k, n in v.total.items():
                out.total[k] = out.total.get(k, 0) + n
        return out

    @classmethod
    def from_heartbeat(cls, hb) -> "WarmthView":
        """Service tier: copy a heartbeat's advertised warm state into a
        routable (mutable, snapshot-local) view."""
        return cls(dict(hb.warm_idle), dict(hb.warm_total))


# ---------------------------------------------------------------------------
# Snapshots
# ---------------------------------------------------------------------------

@dataclass
class ManagerInfo:
    """What a manager advertises to the endpoint agent (paper §6.2)."""
    manager_id: str
    idle_workers: int
    queued: int
    warm_idle: Dict[str, int]          # warmth_key → idle workers warm
    warm_total: Dict[str, int]         # warmth_key → workers warm
    capacity: int                      # total workers
    local_keys: frozenset = frozenset()  # store keys held locally

    @property
    def free_room(self) -> int:
        return max(self.capacity - self.queued, 0)

    @property
    def warmth(self) -> WarmthView:
        """Write-through view over this snapshot's warm dicts."""
        return WarmthView(self.warm_idle, self.warm_total)


# ---------------------------------------------------------------------------
# Shared policy plumbing (both tiers)
# ---------------------------------------------------------------------------

class _SeededPolicy:
    """Shared seeded-RNG handling: every router in both tiers takes a
    ``seed`` and draws from its own ``random.Random`` (reproducible
    benchmarks, no cross-policy interference)."""

    name = "abstract"

    def __init__(self, seed: int = 0):
        self.rng = random.Random(seed)


# ---------------------------------------------------------------------------
# Manager-tier routers
# ---------------------------------------------------------------------------

class Router(_SeededPolicy):
    """Manager-tier routing policy. Policies implement
    :meth:`route_ctx`; :meth:`route` is the stable entry point and
    requires a :class:`RoutingContext`."""

    def route(self, ctx: RoutingContext,
              managers: Sequence[ManagerInfo]) -> Optional[str]:
        return self.route_ctx(ctx, managers)

    def route_ctx(self, ctx: RoutingContext,
                  managers: Sequence[ManagerInfo]) -> Optional[str]:
        raise NotImplementedError


class RandomRouter(Router):
    """Paper's baseline: uniformly random among managers with room."""

    name = "random"

    def route_ctx(self, ctx, managers):
        if not managers:
            return None
        with_room = [m for m in managers if m.free_room > 0]
        pool = with_room or list(managers)
        return self.rng.choice(pool).manager_id


class WarmingAwareRouter(Router):
    """Paper §6.2 generalized to warmth keys: warm-idle on the primary
    key first (most available warm workers wins), then warm-idle on the
    fallback key (container warm, jit cold), then warm-but-busy in the
    same key order (queue behind the warm copy rather than cold-start),
    then the cold fallback."""

    name = "warming_aware"

    def route_ctx(self, ctx, managers):
        if not managers:
            return None
        for key in ctx.warmth_keys:
            warm = [m for m in managers if m.warmth.warm_idle(key) > 0]
            if warm:
                best = max(warm, key=lambda m: m.warmth.warm_idle(key))
                return best.manager_id
        # second chance: warm-but-busy (task queues behind a warm worker,
        # still avoiding a cold start)
        for key in ctx.warmth_keys:
            warm_busy = [m for m in managers
                         if m.warmth.warm_total(key) > 0
                         and m.free_room > 0]
            if warm_busy:
                best = max(warm_busy,
                           key=lambda m: m.warmth.warm_total(key))
                return best.manager_id
        return self._cold(ctx, managers)

    def _cold(self, ctx, managers) -> Optional[str]:
        with_room = [m for m in managers if m.free_room > 0]
        pool = with_room or list(managers)
        return self.rng.choice(pool).manager_id


class WarmingHashRouter(WarmingAwareRouter):
    """Beyond-paper: warming-aware with a *consistent-hash* cold fallback.

    The paper falls back to uniform random when no warm copy exists,
    which scatters each warmth key across all managers and (under slot
    pressure) thrashes the caches. Hashing the key onto the manager ring
    creates key→manager affinity from the very first task, so the fleet
    converges to a stable specialization without any coordination."""

    name = "warming_hash"

    def _cold(self, ctx, managers):
        ordered = sorted(managers, key=lambda m: m.manager_id)
        h = hash(ctx.key)
        for probe in range(len(ordered)):        # linear probe past full ones
            m = ordered[(h + probe) % len(ordered)]
            if m.free_room > 0:
                return m.manager_id
        return ordered[h % len(ordered)].manager_id


class CostAwareRouter(Router):
    """Beyond-paper: minimize expected completion = queue_wait + cold_cost.

    ``cold_cost(key)`` defaults to the endpoint's running mean of measured
    build times per warmth key (fed by :meth:`observe_build` — the agent
    reports every cold build it sees, see DESIGN.md §10);
    ``mean_task_s`` estimates queue drain rate."""

    name = "cost_aware"

    def __init__(self, seed: int = 0, default_cold_cost: float = 1.0,
                 mean_task_s: float = 0.05):
        super().__init__(seed)
        self.default_cold_cost = default_cold_cost
        self.mean_task_s = mean_task_s
        self._costs: Dict[str, float] = {}
        self._lock = threading.Lock()

    def observe_build(self, warmth_key: str, seconds: float) -> None:
        with self._lock:
            prev = self._costs.get(warmth_key)
            self._costs[warmth_key] = (seconds if prev is None
                                       else 0.8 * prev + 0.2 * seconds)

    def cold_cost(self, warmth_key: str) -> float:
        with self._lock:
            return self._costs.get(warmth_key, self.default_cold_cost)

    def route_ctx(self, ctx, managers):
        if not managers:
            return None

        def score(m: ManagerInfo) -> float:
            wait = (m.queued / max(m.capacity, 1)) * self.mean_task_s
            cold = 0.0
            if not any(m.warmth.warm_total(k) > 0 for k in ctx.warmth_keys):
                cold = self.cold_cost(ctx.key)
            elif m.warmth.warm_total(ctx.key) == 0:
                # container warm, refined artifact (jit) still to build
                cold = self.cold_cost(ctx.key) \
                    - min(self.cold_cost(ctx.container_type),
                          self.cold_cost(ctx.key))
            # small jitter to spread exact ties
            return wait + cold + self.rng.random() * 1e-6

        return min(managers, key=score).manager_id


class LocalityAwareRouter(WarmingAwareRouter):
    """Beyond-paper: among equally-warm managers prefer data locality."""

    name = "locality_aware"

    def route_ctx(self, ctx, managers):
        if not managers:
            return None
        key = ctx.key
        warm = [m for m in managers if m.warmth.warm_idle(key) > 0]
        if warm and ctx.input_keys:
            def overlap(m):
                return len(ctx.input_keys & m.local_keys)
            best = max(warm, key=lambda m: (overlap(m),
                                            m.warmth.warm_idle(key)))
            return best.manager_id
        return super().route_ctx(ctx, managers)


# ---------------------------------------------------------------------------
# Federation-level routing (paper §4.1 + §6.2 lifted one tier up): the
# service picks an *endpoint* for a task submitted without one, the same
# way an endpoint agent picks a manager. Endpoint state comes from the
# ForwarderPool: service-side queue depth + in-flight counts are first-hand,
# endpoint-internal load and warm state ride in on heartbeats.
# ---------------------------------------------------------------------------

@dataclass
class EndpointInfo:
    """What the service knows about one endpoint when routing across the
    federation (the endpoint-level analogue of ``ManagerInfo``)."""
    endpoint_id: str
    connected: bool = True
    service_queue: int = 0             # tasks queued service-side
    in_flight: int = 0                 # dispatched, result not yet back
    queued: int = 0                    # heartbeat: pending inside endpoint
    idle_workers: int = 0              # heartbeat
    capacity: int = 0                  # heartbeat: total workers
    warm_idle: Dict[str, int] = field(default_factory=dict)
    warm_total: Dict[str, int] = field(default_factory=dict)

    @property
    def backlog(self) -> int:
        return self.service_queue + self.in_flight + self.queued

    @property
    def load(self) -> float:
        """Backlog normalized by capacity (uncapacitated endpoints —
        heartbeat not seen yet — count as capacity 1)."""
        return self.backlog / max(self.capacity, 1)

    @property
    def warmth(self) -> WarmthView:
        """Write-through view over this snapshot's warm dicts."""
        return WarmthView(self.warm_idle, self.warm_total)

    def note_pick(self, key) -> None:
        """Feed a routing pick back into this snapshot (queue depth up,
        warm-idle and idle-workers down) so consecutive picks from the
        same snapshot — a routed batch or coalesced flush — spread over
        the fleet instead of all landing on the momentary best endpoint.
        ``key`` is a warmth key or a RoutingContext."""
        self.service_queue += 1
        self.warmth.note_pick(key.key if isinstance(key, RoutingContext)
                              else key)
        if self.idle_workers > 0:
            self.idle_workers -= 1


class EndpointRouter(_SeededPolicy):
    """Federation-tier routing policy. Policies implement
    :meth:`select_ctx`; :meth:`select` is the stable entry point and
    requires a :class:`RoutingContext`."""

    def select(self, ctx: RoutingContext,
               endpoints: Sequence[EndpointInfo]) -> Optional[str]:
        return self.select_ctx(ctx, endpoints)

    def select_ctx(self, ctx: RoutingContext,
                   endpoints: Sequence[EndpointInfo]) -> Optional[str]:
        raise NotImplementedError

    def select_many(self, ctx: RoutingContext,
                    endpoints: Sequence[EndpointInfo],
                    n: int) -> List[str]:
        """``n`` picks against one snapshot, with each pick fed back via
        :meth:`EndpointInfo.note_pick` before the next — the per-flush
        grouping primitive for coalesced submissions (DESIGN.md §8).
        Stops short (returned list < ``n``) only if the policy returns
        no endpoint."""
        out: List[str] = []
        for _ in range(n):
            eid = self.select_ctx(ctx, endpoints)
            if eid is None:
                break
            for e in endpoints:
                if e.endpoint_id == eid:
                    e.note_pick(ctx)
                    break
            out.append(eid)
        return out

    @staticmethod
    def _candidates(endpoints: Sequence[EndpointInfo]) -> List[EndpointInfo]:
        up = [e for e in endpoints if e.connected]
        return up or list(endpoints)


class RandomEndpointRouter(EndpointRouter):
    """Baseline: uniformly random among connected endpoints."""

    name = "random"

    def select_ctx(self, ctx, endpoints):
        if not endpoints:
            return None
        return self.rng.choice(self._candidates(endpoints)).endpoint_id


class LeastLoadedEndpointRouter(EndpointRouter):
    """Pick the endpoint with the lowest backlog per unit of capacity."""

    name = "least_loaded"

    def select_ctx(self, ctx, endpoints):
        if not endpoints:
            return None
        pool = self._candidates(endpoints)
        return min(pool, key=lambda e: (e.load,
                                        self.rng.random())).endpoint_id


class WarmingAwareEndpointRouter(EndpointRouter):
    """Paper §6.2 at federation scope, generalized to warmth keys:
    endpoints advertising an *idle warm* copy for the primary key win
    (most warm-idle first, least backlog tie-break), then idle-warm on
    the fallback key, then warm-but-busy in the same key order, then
    least-loaded — so the 61 % completion-time win from warming-aware
    manager routing compounds across the fleet."""

    name = "warming_aware"

    def select_ctx(self, ctx, endpoints):
        if not endpoints:
            return None
        pool = self._candidates(endpoints)
        for key in ctx.warmth_keys:
            warm = [e for e in pool if e.warmth.warm_idle(key) > 0]
            if warm:
                best = max(warm, key=lambda e: (e.warmth.warm_idle(key),
                                                -e.backlog))
                return best.endpoint_id
        for key in ctx.warmth_keys:
            warm_busy = [e for e in pool
                         if e.warmth.warm_total(key) > 0]
            if warm_busy:
                best = max(warm_busy,
                           key=lambda e: (e.warmth.warm_total(key),
                                          -e.backlog))
                return best.endpoint_id
        return min(pool, key=lambda e: (e.load,
                                        self.rng.random())).endpoint_id


# ---------------------------------------------------------------------------
# One registry, two tiers
# ---------------------------------------------------------------------------

ROUTERS = {
    "random": RandomRouter,
    "warming_aware": WarmingAwareRouter,
    "warming_hash": WarmingHashRouter,
    "cost_aware": CostAwareRouter,
    "locality_aware": LocalityAwareRouter,
}

ENDPOINT_ROUTERS = {
    "random": RandomEndpointRouter,
    "least_loaded": LeastLoadedEndpointRouter,
    "warming_aware": WarmingAwareEndpointRouter,
}

_TIERS = {"manager": ROUTERS, "endpoint": ENDPOINT_ROUTERS}


def make_router(name: str, tier: str = "manager", **kw):
    """One factory for both tiers: ``make_router("warming_aware")`` builds
    the manager-tier policy an endpoint agent uses;
    ``make_router("warming_aware", tier="endpoint")`` the federation-tier
    policy the service uses."""
    try:
        registry = _TIERS[tier]
    except KeyError:
        raise KeyError(f"unknown routing tier {tier!r}; "
                       f"options: {sorted(_TIERS)}") from None
    try:
        cls = registry[name]
    except KeyError:
        raise KeyError(f"unknown {tier}-tier router {name!r}; "
                       f"options: {sorted(registry)}") from None
    return cls(**kw)
