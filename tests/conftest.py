import time

import pytest

# NOTE: XLA_FLAGS device-count override is deliberately NOT set here —
# tests must see the real single CPU device.


@pytest.fixture
def service():
    """A FuncXService with fast heartbeats + cleanup."""
    from repro.core import FuncXService
    svc = FuncXService(heartbeat_timeout=0.3)
    yield svc
    svc.shutdown()
    time.sleep(0.05)


@pytest.fixture
def client(service):
    from repro.core import FuncXClient
    token = service.register_user("tester")
    return FuncXClient(service, token)


def wait_until(pred, timeout=5.0, interval=0.01):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return False


@pytest.fixture
def tcp_service():
    """A FuncXService with its TCP listener open: (service, client,
    (host, port)). Remote endpoints dial the address and register over
    the wire."""
    from repro.core import FuncXClient, FuncXService
    svc = FuncXService(heartbeat_timeout=0.3)
    token = svc.register_user("tester")
    address = svc.listen()
    yield svc, FuncXClient(svc, token), address
    svc.shutdown()
    time.sleep(0.05)


def start_tcp_endpoint(client, address, **kw):
    """An in-thread endpoint agent on the dialing side of a real TCP
    socket — the federated deployment without the subprocess cost."""
    from repro.core import RemoteEndpointRunner
    kw.setdefault("n_managers", 1)
    kw.setdefault("workers_per_manager", 2)
    kw.setdefault("heartbeat_interval", 0.05)
    runner = RemoteEndpointRunner(
        address, client.endpoint_credentials(), **kw)
    runner.start()
    return runner
