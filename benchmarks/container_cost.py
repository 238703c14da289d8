"""Table 3 — cold container instantiation time per container technology.

TPU adaptation (DESIGN.md §2): the container cold start is the XLA JIT
compile of the function's executable. We measure REAL jit compiles of
reduced model steps (the "Singularity/Shifter" row analogue — heavyweight,
shared-environment builds) and a lightweight python env (the "Docker on
EC2" analogue), plus warm-cache hits.

The compiles run in a child process of their own
(``python -m benchmarks.container_cost <arch>...``): a process that has
touched JAX holds the accelerator, and ``benchmarks.run`` later spawns
fabric endpoints that need it.
"""
from __future__ import annotations

import subprocess
import sys
import time
from typing import List

import numpy as np

from .common import emit


def _measure_arch(arch: str, trials: int = 3) -> List[float]:
    import jax
    from repro.configs import get_reduced_config
    from repro.models import get_model
    from repro.models.knobs import RunKnobs
    from repro.serve import make_prefill

    cfg = get_reduced_config(arch)
    model = get_model(cfg)
    times = []
    for t in range(trials):
        # vary a static attribute so each trial truly recompiles
        knobs = RunKnobs(q_block=16 + 16 * t, kv_block=16 + 16 * t)
        params = model.init(jax.random.PRNGKey(t))
        toks = np.zeros((1, 64), np.int32)
        batch = {"tokens": toks}
        if cfg.family == "audio":
            batch["frames"] = np.zeros((1, 16, cfg.d_model), np.float32)
        if cfg.family == "vlm":
            batch["patches"] = np.zeros(
                (1, cfg.vlm.vision_prefix_len, cfg.d_model), np.float32)
        fn = jax.jit(make_prefill(model, knobs=knobs))
        t0 = time.perf_counter()
        fn(params, batch)[0].block_until_ready()
        times.append(time.perf_counter() - t0)
        # warm call for contrast (only once)
        if t == 0:
            t0 = time.perf_counter()
            fn(params, batch)[0].block_until_ready()
            emit(f"table3/warm_hit/{arch}",
                 (time.perf_counter() - t0) * 1e6, "executable cache hit")
    return times


def _measure_all(archs: List[str]) -> None:
    for arch in archs:
        times = _measure_arch(arch, trials=3)
        emit(f"table3/cold_jit/{arch}/mean", float(np.mean(times)) * 1e6,
             f"min={min(times):.2f}s max={max(times):.2f}s "
             f"(paper: Theta Singularity 10.4s mean)")


def run(full: bool = False) -> None:
    archs = ["qwen1.5-0.5b", "mamba2-370m", "granite-moe-1b-a400m"]
    if full:
        archs += ["recurrentgemma-9b", "minicpm3-4b"]
    child = subprocess.run(
        [sys.executable, "-m", "benchmarks.container_cost", *archs],
        stdout=subprocess.PIPE, text=True, check=True)
    for row in child.stdout.splitlines():
        name, value, derived = row.split(",", 2)
        emit(name, float(value), derived)
    # lightweight env (the EC2/Docker row): simulated container spawn
    from repro.core import ContainerRegistry, ContainerSpec, WarmCache
    reg = ContainerRegistry()
    reg.register(ContainerSpec("light", simulated_cold_start=0.02))
    cache = WarmCache(reg, slots=1)
    t0 = time.perf_counter()
    cache.get_or_build("light")
    emit("table3/cold_sim/light_env", (time.perf_counter() - t0) * 1e6,
         "(paper: EC2 Docker 1.79s mean)")


if __name__ == "__main__":
    _measure_all(sys.argv[1:])
