"""Fault tolerance (paper §4.1/§4.3): manager loss → re-execution;
endpoint disconnect → forwarder requeue; retry budget → LOST; straggler
speculation; elastic provisioning; socket-transport faults (mid-frame
disconnect, partial length prefix, reconnect after a service restart)."""
import socket
import struct
import time

import pytest

from repro.core import (ContainerSpec, ElasticStrategy, LocalProvider, SimCloudProvider,
                        SimSlurmProvider, TaskFailure, TaskLost, TcpListener)
from repro.core.comms import TO_SERVICE
from repro.core.endpoint import demo_sleep, demo_square
from conftest import start_tcp_endpoint, wait_until


def test_manager_kill_reexecutes(service, client):
    def slow(data):
        time.sleep(0.2)
        return data["i"]
    fid = client.register_function(slow)
    eid, agent = service.make_endpoint(client.token, "ep", n_managers=2,
                                       workers_per_manager=2,
                                       manager_timeout=0.4)
    ids = client.batch_run([(fid, eid, {"i": i}) for i in range(8)])
    time.sleep(0.15)
    agent.kill_manager(list(agent.managers)[0])
    res = client.get_batch_results(ids, timeout=30)
    assert sorted(res) == list(range(8))
    assert agent.tasks_reexecuted > 0
    agent.stop()


def test_all_managers_dead_then_lost(service, client):
    def slow(data):
        time.sleep(10)
        return 1
    fid = client.register_function(slow)
    eid, agent = service.make_endpoint(client.token, "ep", n_managers=1,
                                       workers_per_manager=1,
                                       manager_timeout=0.3, max_retries=0)
    tid = client.run(fid, eid, data={})
    time.sleep(0.15)
    agent.kill_manager(list(agent.managers)[0])
    with pytest.raises(TaskLost):
        client.get_result(tid, timeout=30)
    agent.stop()


def test_container_build_failure_fails_task_and_worker_survives(service, client):
    """A container whose build raises (a compile error, the device out of
    memory) fails its task with that error; the worker thread lives on
    and serves the next task."""
    def broken_build():
        raise RuntimeError("RESOURCE_EXHAUSTED: out of device memory")
    service.register_container(ContainerSpec("broken", build=broken_build))
    fid_broken = client.register_function(lambda d: d, container_type="broken")
    fid_ok = client.register_function(lambda d: d + 1)
    eid, agent = service.make_endpoint(client.token, "ep", n_managers=1,
                                       workers_per_manager=1)
    ex = client.executor(endpoint_id=eid)
    try:
        with pytest.raises(TaskFailure, match="RESOURCE_EXHAUSTED") as info:
            ex.submit(fid_broken, 1).result(timeout=10)
        assert "broken_build" in info.value.remote_traceback
        assert ex.submit(fid_ok, 1).result(timeout=10) == 2
    finally:
        ex.shutdown(wait=False)
        agent.stop()


def test_disconnect_requeues_and_recovers(service, client):
    fid = client.register_function(lambda d: d["i"])
    eid, agent = service.make_endpoint(client.token, "ep", n_managers=1,
                                       workers_per_manager=2)
    rec = service.endpoints[eid]
    rec.channel.disconnect()
    ids = client.batch_run([(fid, eid, {"i": i}) for i in range(5)])
    time.sleep(0.5)              # tasks parked service-side
    assert all(not service.get_task(t).done for t in ids)
    rec.channel.reconnect()
    res = client.get_batch_results(ids, timeout=30)
    assert sorted(res) == list(range(5))
    agent.stop()


def test_heartbeat_detects_disconnect(service, client):
    eid, agent = service.make_endpoint(client.token, "ep", n_managers=1)
    rec = service.endpoints[eid]
    assert wait_until(lambda: rec.connected, timeout=2)
    rec.channel.disconnect()
    assert wait_until(lambda: not rec.forwarder.endpoint_connected,
                      timeout=3)
    rec.channel.reconnect()
    assert wait_until(lambda: rec.forwarder.endpoint_connected, timeout=3)
    agent.stop()


def test_speculation_rescues_straggler(service, client):
    fid = client.register_function(lambda d: 1)
    eid, agent = service.make_endpoint(
        client.token, "ep", n_managers=2, workers_per_manager=2,
        speculation=True, speculation_min=0.3)
    slow_mgr = list(agent.managers.values())[0]
    for w in slow_mgr.workers:
        w.slowdown = 3.0
    ids = client.batch_run([(fid, eid, {}) for _ in range(16)])
    t0 = time.perf_counter()
    res = client.get_batch_results(ids, timeout=60)
    took = time.perf_counter() - t0
    assert res == [1] * 16
    # without speculation the slow manager's share would cost ~9 s
    # (6 tasks × 3 s / 2 workers); speculation reroutes the stragglers
    assert agent.speculative_dispatches > 0
    assert took < 6.0
    agent.stop()


def test_elastic_scale_out_and_in(service, client):
    def work(data):
        time.sleep(0.05)
        return 0
    fid = client.register_function(work)
    eid, agent = service.make_endpoint(client.token, "ep", n_managers=0)
    strat = ElasticStrategy(agent, LocalProvider(workers_per_node=2),
                            min_blocks=1, max_blocks=4, idle_timeout=0.4,
                            interval=0.03)
    agent.strategy = strat
    strat.start()
    assert wait_until(lambda: strat.blocks() >= 1, timeout=3)
    ids = client.batch_run([(fid, eid, {}) for _ in range(40)])
    res = client.get_batch_results(ids, timeout=60)
    assert len(res) == 40
    assert strat.scale_out_events > 0
    assert wait_until(lambda: strat.blocks() == 1, timeout=10)
    assert strat.scale_in_events > 0
    agent.stop()


def test_provider_delays():
    slurm = SimSlurmProvider(mean_wait=0.05, jitter=0.0)
    cloud = SimCloudProvider(boot_delay=0.03)
    assert slurm.acquisition_delay() >= 0.05
    assert cloud.acquisition_delay() == 0.03


# -- socket transport faults -------------------------------------------------

class _Grab:
    def __init__(self):
        self.transport = None

    def __call__(self, transport, peer):
        self.transport = transport


def test_tcp_partial_length_prefix_is_dropped():
    """A connection that dies inside the 4-byte length prefix delivers
    nothing — no truncated frame, no reader crash."""
    grab = _Grab()
    listener = TcpListener("127.0.0.1", 0, grab)
    try:
        s = socket.create_connection(listener.address)
        assert wait_until(lambda: grab.transport is not None, timeout=5)
        s.sendall(b"\x00\x00")                       # 2 of 4 length bytes
        s.close()
        assert wait_until(lambda: not grab.transport.connected, timeout=5)
        assert grab.transport.frames_in == 0
        assert grab.transport.recv(TO_SERVICE, timeout=0.1) is None
    finally:
        listener.close()


def test_tcp_midframe_disconnect_is_dropped():
    """A frame cut short mid-body is discarded with the connection; the
    frames before the cut still arrive intact."""
    grab = _Grab()
    listener = TcpListener("127.0.0.1", 0, grab)
    try:
        s = socket.create_connection(listener.address)
        assert wait_until(lambda: grab.transport is not None, timeout=5)
        whole = b"intact-frame"
        s.sendall(struct.pack(">I", len(whole)) + whole)
        s.sendall(struct.pack(">I", 100) + b"only ten b")   # then die
        s.close()
        assert wait_until(lambda: grab.transport.frames_in == 1, timeout=5)
        assert grab.transport.recv(TO_SERVICE, timeout=1.0) == whole
        assert wait_until(lambda: not grab.transport.connected, timeout=5)
        assert grab.transport.recv(TO_SERVICE, timeout=0.1) is None
    finally:
        listener.close()


def test_tcp_connection_kill_midload_completes_exactly_once(tcp_service):
    """Kill the socket while a batch is in flight: requeue-on-disconnect +
    re-dial + re-register deliver every submitted task exactly one
    completion (duplicate executions are deduped at the result store)."""
    svc, client, address = tcp_service
    runner = start_tcp_endpoint(client, address)
    try:
        fid = client.register_function(demo_square)
        ids = client.batch_run([(fid, runner.endpoint_id, {"x": i})
                                for i in range(30)])
        runner.transport.disconnect()                # mid-stream cut
        runner.transport.reconnect()                 # allow the re-dial
        res = client.get_batch_results(ids, timeout=60)
        assert res == [i * i for i in range(30)]
        assert runner.re_registrations >= 1
        # exactly once: every id was retrieved once and then purged
        for tid in ids:
            with pytest.raises(KeyError):
                svc.get_task(tid)
    finally:
        runner.stop()


def test_results_finished_during_outage_are_retransmitted(tcp_service):
    """A result produced while the link is down must be parked and
    retransmitted after the re-dial — not swallowed by the duplicate
    filter when the requeued task re-executes (regression: these tasks
    used to hang forever)."""
    svc, client, address = tcp_service
    runner = start_tcp_endpoint(client, address, workers_per_manager=4)
    try:
        fid = client.register_function(demo_sleep)
        ids = client.batch_run([(fid, runner.endpoint_id, {"s": 0.3})
                                for _ in range(4)])
        # cut the link while all four are mid-execution
        assert wait_until(lambda: runner.agent.tasks_received >= 4,
                          timeout=5)
        runner.transport.disconnect()
        time.sleep(1.0)          # tasks finish into a dead link
        runner.transport.reconnect()
        res = client.get_batch_results(ids, timeout=30)
        assert res == [None] * 4
    finally:
        runner.stop()


def test_tcp_reconnect_after_service_restart_completes_all(tcp_service):
    """Service network tier goes down (listener closed, channel dead) and
    comes back on the same port: the endpoint re-dials, re-registers under
    its old id, in-flight work is requeued, and everything submitted —
    before and during the outage — completes exactly once."""
    svc, client, address = tcp_service
    host, port = address
    runner = start_tcp_endpoint(client, address)
    try:
        fid = client.register_function(demo_square)
        before = client.batch_run([(fid, runner.endpoint_id, {"x": i})
                                   for i in range(10)])
        rec = svc.endpoints[runner.endpoint_id]
        svc.stop_listening()
        rec.channel.close()                          # "service restart"
        during = client.batch_run([(fid, runner.endpoint_id, {"x": i})
                                   for i in range(10, 20)])
        time.sleep(0.3)                              # endpoint is re-dialing
        svc.listen(host, port)                       # service back up
        res = client.get_batch_results(before + during, timeout=60)
        assert res == [i * i for i in range(20)]
        assert runner.re_registrations >= 1
        assert svc.endpoints[runner.endpoint_id].channel is not rec.channel \
            or rec.channel.connected
    finally:
        runner.stop()


def test_forwarder_pool_restart_by_health_check(service, client):
    fid = client.register_function(lambda d: d)
    eid, agent = service.make_endpoint(client.token, "ep", n_managers=1)
    old_pool = service.pool
    old_pool._stop.set()             # simulates crashed loops → unhealthy
    assert wait_until(lambda: service.pool is not old_pool, timeout=5)
    assert service.forwarder_restarts >= 1
    # the record's line was swapped onto the new pool
    assert service.endpoints[eid].line is service.pool.line(eid)
    tid = client.run(fid, eid, data=9)
    assert client.get_result(tid, timeout=10) == 9
    agent.stop()
