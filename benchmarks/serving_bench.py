"""DESIGN.md §10 — serving fabric: sustained mixed-model load over real
socket endpoints, jit-cache-aware routing vs random.

Two identical subprocess fleets serve the same interleaved two-model
request stream through ``FuncXExecutor``. Each endpoint runs ONE worker
with ONE warm slot, so the fleet can hold each model's jit-compiled
executable warm exactly once — the *aware* lane (service endpoint_router
``warming_aware``) reads the jit warmth keys off heartbeats and keeps
each model pinned to its warm endpoint, while the *random* lane scatters
requests and pays the ``jax.jit`` recompile every time a model lands on
the endpoint that last served the other one. Emits per-lane p50/p99
latency and the warm-hit rate (from the env-held uses counter each
serving call reports), which ``tools/bench_gate.py --serving`` gates on:
warmth-aware routing must beat (or tie) random on warm-hit rate.

The models are the ``@smoke`` (toy-size) configs. Every result names the
platform that served it, and the run fails if they differ: an endpoint
that could not get the accelerator and fell back to the CPU is an error,
not a data point.
"""
from __future__ import annotations

import itertools
import subprocess
import threading
import time

import numpy as np

from .common import emit

ARCHS = ("qwen1.5-0.5b@smoke", "mamba2-370m@smoke")


def _pct(sorted_vals, q: float) -> float:
    if not sorted_vals:
        return float("nan")
    i = min(int(q * len(sorted_vals)), len(sorted_vals) - 1)
    return sorted_vals[i]


def serving_lane(router: str, requests: int, *, n_endpoints: int = 2,
                 concurrency: int = 2, timeout: float = 300.0):
    """One fleet under one endpoint-router policy. Closed-loop clients:
    ``concurrency`` threads each submit-and-wait through the executor
    (executor.submit → submit_packed_batch → select_many is the routed
    path under test). Returns (sorted latencies, warm-hit rate, req/s,
    the set of platforms that served)."""
    from repro.core import FuncXClient, FuncXService
    from repro.core.endpoint import spawn_endpoint_process
    from repro.serve import fabric

    svc = FuncXService(heartbeat_timeout=2.0, shm=False,
                       endpoint_router=router)
    procs = []
    try:
        tok = svc.register_user("bench")
        client = FuncXClient(svc, tok)
        zoo = fabric.register_zoo(client, ARCHS)
        address = svc.listen()
        cred = client.endpoint_credentials()
        eids = []
        for i in range(n_endpoints):
            p, eid = spawn_endpoint_process(
                address, cred, name=f"serve-{router}-{i}", workers=1,
                shm=False, peer=False,
                containers="repro.serve.fabric:install")
            procs.append(p)
            eids.append(eid)

        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, 100, (1, 9)).astype(np.int32)
                   for _ in range(requests)]
        ex = client.executor(batch_size=8)

        # Prewarm: seed exactly one warm jit cache per model, pinned
        # round-robin over the fleet — the deployment's prewarm step, and
        # identical in both lanes. The measured stream then gauges steady
        # -state routing quality, not the unavoidable first compiles.
        platforms = set()
        for i, arch in enumerate(ARCHS):
            fid, ct = zoo[arch]
            out = ex.submit(fid, {"tokens": prompts[0], "n_tokens": 2,
                                  "seed": 0},
                            endpoint_id=eids[i % n_endpoints],
                            container_type=ct).result(timeout=timeout)
            platforms.add(out["platform"])
        lock = threading.Lock()
        lats, warm_hits = [], [0]
        counter = itertools.count()

        def closed_loop():
            while True:
                i = next(counter)
                if i >= requests:
                    return
                fid, ct = zoo[ARCHS[i % len(ARCHS)]]
                t0 = time.perf_counter()
                fut = ex.submit(fid, {"tokens": prompts[i], "n_tokens": 2,
                                      "seed": i}, container_type=ct)
                out = fut.result(timeout=timeout)
                dt = time.perf_counter() - t0
                with lock:
                    lats.append(dt)
                    warm_hits[0] += bool(out["warm"])
                    platforms.add(out["platform"])

        t0 = time.perf_counter()
        threads = [threading.Thread(target=closed_loop, daemon=True)
                   for _ in range(concurrency)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        ex.shutdown()
        return (sorted(lats), warm_hits[0] / max(requests, 1),
                requests / wall, platforms)
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                p.kill()
        svc.shutdown()


def run(full: bool = False, tiny: bool = False) -> None:
    if tiny:
        requests = 10
    elif full:
        requests = 48
    else:
        requests = 24

    aware_lats, aware_rate, aware_rps, aware_on = serving_lane(
        "warming_aware", requests)
    rand_lats, rand_rate, rand_rps, rand_on = serving_lane("random", requests)
    platforms = aware_on | rand_on
    if len(platforms) != 1:
        raise RuntimeError(f"endpoints served on different platforms: "
                           f"{sorted(platforms)}")

    for label, lats, rate, rps in [
            ("aware", aware_lats, aware_rate, aware_rps),
            ("random", rand_lats, rand_rate, rand_rps)]:
        emit(f"serving/{label}/p50_ms", _pct(lats, 0.50) * 1e3,
             f"requests={requests} archs={len(ARCHS)} "
             f"platform={next(iter(platforms))}")
        emit(f"serving/{label}/p99_ms", _pct(lats, 0.99) * 1e3, "")
        emit(f"serving/{label}/warm_hit_rate", rate,
             f"req_per_s={rps:.2f}")
    # the gated invariant: jit-cache-aware routing keeps the executables
    # pinned — it must never lose to scattering on warm-hit rate
    emit("serving/warm_hit_advantage", aware_rate - rand_rate,
         f"aware={aware_rate:.2f} random={rand_rate:.2f}")
