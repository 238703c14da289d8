"""Federated learning over funcX endpoints (paper §8 — the Flox case
study), on the real fabric:

    PYTHONPATH=src python examples/federated_learning.py

Two "edge" endpoints run as separate OS processes connected over TCP.
Each round, ``fedavg_local_train`` fans out through the futures-native
FuncXExecutor with a ``warmth_key`` naming the jitted train step
(DESIGN.md §10), so round 2+ lands on the worker that already compiled
it. The endpoints' ``stage_limit`` sits below the raw delta size, so
every local delta leaves its endpoint as a cross-endpoint **DataRef** —
the aggregation task (pinned to edge-0) pulls the other endpoints'
deltas peer-direct over the data plane (DESIGN.md §9), and only the
int8-compressed mean rides the hub back to the coordinator. The
self-check asserts the transport shape: deltas travelled as refs, and
zero delta bytes transited the hub relay.
"""
import subprocess
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.core import FuncXClient, FuncXService
from repro.core.endpoint import spawn_endpoint_process
from repro.data import DataRef
from repro.models import get_model
from repro.train import (
    FedAvgCoordinator,
    fedavg_aggregate,
    fedavg_local_train,
    train_warmth_key,
)

ARCH = "qwen1.5-0.5b@smoke"         # the toy size; a bare id is published width
N_ENDPOINTS = 2
ROUNDS = 3


def main():
    # The edge endpoints train on the accelerator, and a chip belongs to
    # one process: the coordinator keeps its own (small) arrays on the CPU.
    jax.config.update("jax_platforms", "cpu")
    cfg = get_config(ARCH)
    model = get_model(cfg)
    delta_nbytes = model.param_count() * np.dtype(np.float32).itemsize
    params = model.init(jax.random.PRNGKey(0))

    service = FuncXService(heartbeat_timeout=2.0, shm=False)
    token = service.register_user("fl-coordinator")
    client = FuncXClient(service, token)
    fid_train = client.register_function(fedavg_local_train,
                                         name="flox/local_train")
    fid_agg = client.register_function(fedavg_aggregate,
                                       name="flox/aggregate")
    address = service.listen()
    cred = client.endpoint_credentials()

    # stage_limit below the raw delta size: every local_train result
    # becomes a DataRef parked in its endpoint's store; the compressed
    # mean (~4x smaller) still fits inline on the way back
    procs, eids = [], []
    for i in range(N_ENDPOINTS):
        p, eid = spawn_endpoint_process(
            address, cred, name=f"edge-{i}", workers=1, shm=False,
            stage_limit=delta_nbytes // 2)
        procs.append(p)
        eids.append(eid)
    print(f"federation: {N_ENDPOINTS} edge endpoints (subprocesses), "
          f"delta={delta_nbytes / 1e6:.2f} MB, "
          f"stage_limit={delta_nbytes // 2 / 1e6:.2f} MB")

    coord = FedAvgCoordinator(client, fid_train, eids, method="int8")
    t0 = time.perf_counter()
    try:
        with client.executor(batch_size=8) as ex:
            for rnd in range(ROUNDS):
                params, metrics, parts = coord.round_refs(
                    params, arch=ARCH, executor=ex, aggregate_fn=fid_agg,
                    local_steps=4, seed=rnd)
                assert all(isinstance(p, DataRef) for p in parts), \
                    "deltas should leave the edges as refs, not values"
                print(f"round {rnd}: mean local loss "
                      f"{metrics['mean_loss']:.4f}  compression "
                      f"{metrics['compression_ratio']:.1f}x  "
                      f"(warmth_key={train_warmth_key(ARCH, 8)})")
        # the aggregate pulled edge-1's delta peer-direct; nothing heavy
        # ever transited the hub
        assert service.hub_relays == 0 and service.hub_relay_bytes == 0, \
            "delta bytes took the hub relay"
        print(f"{ROUNDS} rounds in {time.perf_counter() - t0:.1f}s; "
              f"{coord.bytes_sent / 1e6:.2f} MB coordinator-bound "
              f"(vs {coord.bytes_uncompressed / 1e6:.2f} MB raw), "
              f"hub relay bytes={service.hub_relay_bytes}")
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                p.kill()
        service.shutdown()


if __name__ == "__main__":
    main()
