"""Hierarchical interchange (DESIGN.md §11): upstream it is one ordinary
endpoint, downstream a mini-forwarder over the identical wire protocol.
Pinned here: burst absorption into the deep backlog, credit backpressure
on the service-side forwarder, heartbeat synthesis (aggregate load +
merged warmth), exactly-once through leaf death and upstream cuts,
relay-of-relays nesting, and the elastic leaf lifecycle."""
import time

import pytest

from repro.core import (
    ElasticStrategy,
    Interchange,
    ThreadLeafProvider,
)
from conftest import wait_until


@pytest.fixture
def relay(tcp_service):
    """(svc, client, interchange) — an interchange registered upstream,
    no leaves yet (each test attaches what it needs)."""
    svc, client, (host, port) = tcp_service
    ix = Interchange(f"{host}:{port}", client.endpoint_credentials(),
                     name="relay", depth=5000, heartbeat_interval=0.05,
                     leaf_timeout=0.4)
    ix.start()
    yield svc, client, ix
    ix.stop()


def add_leaves(ix, n, *, workers=2, **kw):
    prov = ThreadLeafProvider(ix, workers_per_node=workers, **kw)
    ids = []
    for _ in range(n):
        ids += prov.start_block(ix)
    return prov, ids


# ---------------------------------------------------------------- basic relay

def test_relay_roundtrip(relay):
    svc, client, ix = relay
    prov, _ = add_leaves(ix, 2)
    try:
        fid = client.register_function(lambda d: d["i"] * 3)
        ids = client.batch_run([(fid, ix.endpoint_id, {"i": i})
                                for i in range(20)])
        assert client.get_batch_results(ids, timeout=30) == \
            [3 * i for i in range(20)]
        # pack-once held: every task crossed both hops, every result came
        # back through the relay
        assert ix.tasks_received == 20
        assert ix.results_forwarded == 20
    finally:
        prov.stop_all()


def test_result_racing_ahead_of_send_bookkeeping_does_not_leak(relay):
    """A fast leaf can return a result before the dispatcher re-acquires
    the lock after sending. The in-flight entry must exist by the time
    the result lands, or the pop misses and the leaf's dispatch window
    leaks one unit forever (at 100k scale the leaks freeze dispatch with
    work still in the backlog). Simulate the worst case: the result
    arrives synchronously *inside* the send call."""
    svc, client, ix = relay
    prov, _ = add_leaves(ix, 1)
    try:
        fid = client.register_function(lambda d: d["i"])
        line = ix.leaf_lines()[0]
        real_send = line.channel.send_parts_to_endpoint
        from repro.core.protocol import ResultBatch, ResultMsg, from_wire

        def racing_send(env, segs, tag="tasks"):
            ok = real_send(env, segs, tag=tag)
            if ok and tag == "tasks":
                batch = from_wire({**env, "_segs": segs})
                ix._leaf_results(line, ResultBatch(results=[
                    ResultMsg(task_id=s.task_id, result=None)
                    for s in batch.tasks]))
            return ok

        line.channel.send_parts_to_endpoint = racing_send
        ids = client.batch_run([(fid, ix.endpoint_id, {"i": i})
                                for i in range(8)])
        client.get_batch_results(ids, timeout=30)
        # the synchronous results must have found their in-flight entries
        assert wait_until(lambda: line.in_flight_count() == 0, timeout=5)
        assert line.window(ix.leaf_window, ix.queue_factor) > 0
    finally:
        line.channel.send_parts_to_endpoint = real_send
        prov.stop_all()


def test_heartbeat_synthesizes_subtree(relay):
    """Upstream sees one endpoint whose heartbeat aggregates the whole
    subtree: summed capacity, merged warm dicts, backlog credits."""
    svc, client, ix = relay
    prov, _ = add_leaves(ix, 2, workers=2)
    try:
        line = svc.pool.line(ix.endpoint_id)
        assert wait_until(lambda: line.advertised.capacity == 4, timeout=5)
        hb = line.advertised
        assert hb.credits >= 0                   # bounded intake advertised
        assert hb.credits <= ix.depth
        assert hb.depth == ix.depth
        # warm a container on the leaves, then the merged dicts show it
        fid = client.register_function(lambda d: d)
        ids = client.batch_run([(fid, ix.endpoint_id, i) for i in range(4)])
        assert client.get_batch_results(ids, timeout=30) == list(range(4))
        assert wait_until(
            lambda: svc.pool.line(ix.endpoint_id).advertised.warm_idle.get(
                "python", 0) > 0, timeout=5)
    finally:
        prov.stop_all()


def test_backlog_absorbs_burst_before_any_leaf_exists(relay):
    """The tentpole queueing property: a burst lands entirely in the
    interchange backlog (acked upstream, nothing dispatched) and drains
    the moment leaves appear."""
    svc, client, ix = relay
    fid = client.register_function(lambda d: d["i"])
    ids = client.batch_run([(fid, ix.endpoint_id, {"i": i})
                            for i in range(500)])
    assert wait_until(lambda: ix.backlog_peak >= 500, timeout=10)
    assert ix.tasks_dispatched == 0
    # the service-side line drained into the relay (acked, in flight)
    assert wait_until(
        lambda: svc.pool.line(ix.endpoint_id).queue_len() == 0, timeout=5)
    prov, _ = add_leaves(ix, 2)
    try:
        assert client.get_batch_results(ids, timeout=60) == list(range(500))
    finally:
        prov.stop_all()


def test_credits_backpressure_caps_service_dispatch(tcp_service):
    """A shallow relay advertises few credits; the service-side forwarder
    must stop at the advertisement instead of overrunning the bounded
    intake — the rest of the burst waits service-side."""
    svc, client, (host, port) = tcp_service
    ix = Interchange(f"{host}:{port}", client.endpoint_credentials(),
                     name="shallow", depth=50, heartbeat_interval=0.05)
    ix.start()
    try:
        line = svc.pool.line(ix.endpoint_id)
        # wait for the first credit advertisement so the cap is in force
        assert wait_until(lambda: line.advertised.credits >= 0, timeout=5)
        fid = client.register_function(lambda d: d["i"])
        ids = client.batch_run([(fid, ix.endpoint_id, {"i": i})
                                for i in range(200)])
        assert wait_until(lambda: ix.tasks_received == 50, timeout=5)
        time.sleep(0.3)                          # several credit refreshes
        assert ix.tasks_received == 50           # no overrun past depth
        assert line.queue_len() == 150
        # leaves drain the backlog; freed credits let the rest flow
        prov, _ = add_leaves(ix, 2)
        try:
            assert client.get_batch_results(ids, timeout=60) == \
                list(range(200))
        finally:
            prov.stop_all()
    finally:
        ix.stop()


# ------------------------------------------------------------- exactly-once

def test_leaf_death_requeues_and_completes_exactly_once(relay):
    """Kill one leaf mid-burst (no goodbye — heartbeats just stop): its
    in-flight specs requeue into the backlog and finish on the survivor;
    every task completes exactly once upstream."""
    svc, client, ix = relay
    prov, leaf_ids = add_leaves(ix, 2, workers=1)
    try:
        fid = client.register_function(
            lambda d: time.sleep(0.02) or d["i"])
        ids = client.batch_run([(fid, ix.endpoint_id, {"i": i})
                                for i in range(40)])
        victim = leaf_ids[0]
        assert wait_until(
            lambda: any(ln.endpoint_id == victim and ln.dispatched > 0
                        for ln in ix.leaf_lines()), timeout=10)
        # abrupt death: stop the runner without telling the interchange
        prov._runners.pop(victim).stop()
        assert client.get_batch_results(ids, timeout=60) == list(range(40))
        assert ix.requeues > 0
        # exactly once: purge-on-get means a second fetch must fail
        for tid in ids:
            with pytest.raises(KeyError):
                svc.get_task(tid)
    finally:
        prov.stop_all()


def test_upstream_cut_parks_results_and_retransmits(relay):
    """Results produced while the service link is down park in the
    interchange and retransmit after the automatic re-register — nothing
    is lost, nothing duplicates."""
    svc, client, ix = relay
    prov, _ = add_leaves(ix, 1)
    try:
        # each task outlasts the moment of the cut, so no result can reach
        # the service before it: every one is produced into the outage or
        # after, and comes back only over the re-registered link
        fid = client.register_function(
            lambda d: __import__("time").sleep(0.3) or d["i"] * 2)
        ids = client.batch_run([(fid, ix.endpoint_id, {"i": i})
                                for i in range(10)])
        assert wait_until(lambda: ix.backlog_peak >= 1 or
                          ix.tasks_received == 10, timeout=10)
        ix.transport.disconnect()               # upstream cut
        time.sleep(0.5)                         # results finish into it
        ix.transport.reconnect()                # allow the re-dial
        assert client.get_batch_results(ids, timeout=60) == \
            [2 * i for i in range(10)]
        assert ix.re_registrations >= 1
        for tid in ids:
            with pytest.raises(KeyError):
                svc.get_task(tid)
    finally:
        prov.stop_all()


# ------------------------------------------------------------------- nesting

def test_relay_of_relays_two_levels(relay):
    """An interchange registers with another interchange exactly like a
    leaf does — the downstream handshake is the service's. Tasks cross
    service → relay → child-relay → leaf and back."""
    svc, client, ix = relay
    child = Interchange(ix.leaf_address, ix.leaf_token, name="child",
                        depth=2000, heartbeat_interval=0.05,
                        leaf_timeout=0.4)
    child.start()
    prov, _ = add_leaves(child, 2)
    try:
        # the parent sees the child's bounded intake like the service
        # sees the parent's
        assert wait_until(
            lambda: any(ln.advertised.credits >= 0
                        for ln in ix.leaf_lines()), timeout=5)
        fid = client.register_function(lambda d: d["i"] + 100)
        ids = client.batch_run([(fid, ix.endpoint_id, {"i": i})
                                for i in range(30)])
        assert client.get_batch_results(ids, timeout=60) == \
            [i + 100 for i in range(30)]
        assert child.results_forwarded == 30
        assert ix.results_forwarded == 30
    finally:
        prov.stop_all()
        child.stop()


# ------------------------------------------------------------------ elastic

def test_elastic_leaves_scale_out_on_backlog_and_reap_when_idle(relay):
    svc, client, ix = relay
    prov = ThreadLeafProvider(ix, workers_per_node=2)
    strategy = ElasticStrategy(ix, prov, min_blocks=0, max_blocks=3,
                               backlog_per_block=20, idle_timeout=0.4,
                               interval=0.03)
    ix.strategy = strategy
    strategy.start()
    fid = client.register_function(lambda d: d["i"])
    ids = client.batch_run([(fid, ix.endpoint_id, {"i": i})
                            for i in range(60)])
    # backlog depth of 60 asks for ceil(60/20)=3 blocks in one decision
    assert wait_until(lambda: strategy.scale_out_events >= 3, timeout=10)
    assert client.get_batch_results(ids, timeout=60) == list(range(60))
    # drained + idle past the timeout: leaves reap back to min_blocks
    assert wait_until(lambda: strategy.blocks() == 0, timeout=15)
    assert strategy.scale_in_events >= 3
    assert ix.leaf_lines() == []
