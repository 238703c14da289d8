"""The endpoint-side half of the benchmark.

The harness spawns the endpoint with
``--containers chipbench.remote:install``. :func:`install` seeds the
fabric's weights from the run's seed, sets the fields of the program's
preset that the configuration's file names under ``"set_in_preset"`` to
their published values, gives the endpoint the deployment's manager
heartbeat threshold, meters the process's pauses, counts every
executable the process compiles or loads, and calls ``fabric.install``:
the fabric's own build path is what serves. The functions below are what the harness submits
through ``executor.submit``: two thin wrappers that call the fabric's
served functions inside a profiler annotation, and a few probes that read
the process's device, compile count, peak memory and profiler. No program
file is changed.
"""
from __future__ import annotations

import json
import os
import threading
import time

SEED_ENV = "CHIPBENCH_PARAMS_SEED"
PRESET_ENV = "CHIPBENCH_PRESET"             # {"arch": ..., "fields": {...}}
MANAGER_TIMEOUT_ENV = "CHIPBENCH_MANAGER_TIMEOUT_S"
REQUIRE_ENV = "CHIPBENCH_REQUIRE"           # "<platform>:<chips>"
NO_CHIP = "chipbench-no-chip"
ANNOTATION = {"generate": "chipbench.generate", "prefill": "chipbench.prefill"}


class _Counters:
    """Process-wide counts the probes read: JAX reports compiles to
    listeners, which are process-wide too."""

    def __init__(self):
        self.lock = threading.Lock()
        self.compiles = 0
        self.cache_entries_at_start = 0
        self.tracer = None
        self.span = None
        self.pauses = None
        self.runner = None

    def on_duration(self, event: str, _secs: float, **_kw) -> None:
        from jax._src.dispatch import BACKEND_COMPILE_EVENT
        if event == BACKEND_COMPILE_EVENT:
            with self.lock:
                self.compiles += 1


_COUNTERS = _Counters()


class PauseMeter:
    """The longest stretch in which this process ran none of its Python
    (a thread that asks to wake every ``tick`` seconds, and how late it
    woke), and the longest garbage collection, since the last read.

    It takes no lock: a collection can start at any allocation, in any
    thread, also one that holds a lock the callback would wait for. Each
    maximum has one writer (the meter's thread, the collector), and a
    read that races a write loses at most that one reading."""

    def __init__(self, tick: float = 0.02):
        self.tick = tick
        self.late = self.gc = 0.0
        self._gc_start = None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="chipbench-pauses")

    def start(self) -> "PauseMeter":
        import gc

        gc.callbacks.append(self._on_gc)
        self._thread.start()
        return self

    def stop(self) -> None:
        import gc

        self._stop.set()
        self._thread.join()
        gc.callbacks.remove(self._on_gc)

    def _run(self) -> None:
        while not self._stop.is_set():
            t0 = time.perf_counter()
            time.sleep(self.tick)
            self.late = max(self.late, time.perf_counter() - t0 - self.tick)

    def _on_gc(self, phase: str, _info: dict) -> None:
        if phase == "start":
            self._gc_start = time.perf_counter()
        elif self._gc_start is not None:
            self.gc = max(self.gc, time.perf_counter() - self._gc_start)

    def read(self) -> dict:
        """``{"late_s", "gc_s"}`` since the last read, and start anew."""
        out = {"late_s": self.late, "gc_s": self.gc}
        self.late = self.gc = 0.0
        return out


def _cache_entries(path) -> int:
    return len(os.listdir(path)) if path and os.path.isdir(path) else 0


def install(registry):
    """The ``--containers`` hook: refuse to serve off the required chip,
    seed the weights from the run's seed, set the published preset fields
    and the manager heartbeat threshold, meter pauses, count compiles,
    then install the fabric's own ``jit/`` factory. ``SIGUSR1`` dumps every thread's stack
    to standard error, for a run that stalls."""
    import faulthandler
    import signal

    import jax

    faulthandler.register(signal.SIGUSR1, all_threads=True)

    from repro.compile_cache import enable_compile_cache
    from repro.serve import fabric

    platform, chips = os.environ.get(REQUIRE_ENV, "tpu:1").split(":")
    devices = jax.devices()
    if devices[0].platform != platform or len(devices) < int(chips):
        raise RuntimeError(
            f"{NO_CHIP}: needs {chips} {platform} device(s), JAX found "
            f"{len(devices)} {devices[0].platform!r} ({devices[0].device_kind})")
    _COUNTERS.cache_entries_at_start = _cache_entries(enable_compile_cache())
    seed = os.environ.get(SEED_ENV)
    if seed is not None:
        if not hasattr(fabric, "PARAMS_SEED"):
            raise RuntimeError("repro.serve.fabric has no PARAMS_SEED to seed")
        fabric.PARAMS_SEED = int(seed)
    preset = os.environ.get(PRESET_ENV)
    if preset:
        set_preset(fabric, **json.loads(preset))
    timeout = os.environ.get(MANAGER_TIMEOUT_ENV)
    if timeout:
        set_manager_timeout(float(timeout))
    _COUNTERS.pauses = PauseMeter().start()
    jax.monitoring.register_event_duration_secs_listener(
        _COUNTERS.on_duration)
    return fabric.install(registry)


def set_preset(fabric, arch: str, fields: dict) -> None:
    """Serve ``arch`` with the published value of each of ``fields``
    where the program's preset differs, through the preset's own
    ``with_``; an unknown field, or a fabric that no longer looks its
    configuration up by name, fails the start."""
    if not fields:
        return
    if not hasattr(fabric, "get_config"):
        raise RuntimeError("repro.serve.fabric has no get_config to set")
    base = fabric.get_config
    base(arch).with_(**fields)

    def get_config(name):
        cfg = base(name)
        return cfg.with_(**fields) if name == arch else cfg

    fabric.get_config = get_config


def set_manager_timeout(seconds: float) -> None:
    """Build the endpoint with the deployment's manager heartbeat
    threshold, through ``EndpointAgent``'s own ``manager_timeout``: the
    endpoint's command line, which runs this installer before it builds
    its runner, has no flag for it and leaves the 1 s default."""
    import sys

    main = sys.modules["__main__"]
    base = getattr(main, "RemoteEndpointRunner", None)
    if base is None:
        raise RuntimeError("the endpoint's command line builds no "
                           "RemoteEndpointRunner to set manager_timeout on")

    class Runner(base):
        def __init__(self, *args, **kw):
            kw.setdefault("manager_timeout", seconds)
            super().__init__(*args, **kw)
            _COUNTERS.runner = self

    main.RemoteEndpointRunner = Runner


def generate(data, env):
    """``fabric.serve_generate`` inside a trace annotation; adds the
    worker that served it, so set-up can see both workers warm."""
    import jax

    from repro.serve import fabric

    with jax.profiler.TraceAnnotation(ANNOTATION["generate"]):
        out = fabric.serve_generate(data, env)
    out["worker"] = threading.current_thread().name
    return out


def prefill(data, env):
    """``fabric.serve_prefill`` inside a trace annotation."""
    import jax

    from repro.serve import fabric

    with jax.profiler.TraceAnnotation(ANNOTATION["prefill"]):
        out = fabric.serve_prefill(data, env)
    out["worker"] = threading.current_thread().name
    return out


SERVED = {"generate": generate, "prefill": prefill}


def probe(_data=None):
    """The device this endpoint computes on, the compiles so far, the
    device's peak bytes and the persistent cache's entry count."""
    import jax

    from repro.compile_cache import enable_compile_cache

    devices = jax.devices()
    stats = devices[0].memory_stats() or {}
    peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in devices), default=0)
    cache_dir = enable_compile_cache()
    with _COUNTERS.lock:
        compiles = _COUNTERS.compiles
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices),
            "compiles": compiles, "memory_peak_bytes": int(peak),
            "bytes_in_use": int(stats.get("bytes_in_use", 0)),
            "cache_dir": cache_dir,
            "cache_entries_at_start": _COUNTERS.cache_entries_at_start,
            "cache_entries": _cache_entries(cache_dir),
            "pauses": _COUNTERS.pauses.read() if _COUNTERS.pauses else None,
            "manager_timeout": getattr(getattr(_COUNTERS.runner, "agent",
                                               None), "manager_timeout",
                                       None)}


def trace_start(args):
    """Open the profiler for ``args["seconds"]``: device activity and host
    annotations, no Python function tracer. A thread of this process
    closes it, so no worker waits on the trace."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 2
    opts.python_tracer_level = 0
    jax.profiler.start_trace(args["dir"], profiler_options=opts)
    span = {"dir": args["dir"], "opened": time.perf_counter()}

    def close():
        time.sleep(float(args["seconds"]))
        span["closed"] = time.perf_counter()
        jax.profiler.stop_trace()

    _COUNTERS.tracer = threading.Thread(target=close, name="chipbench-trace")
    _COUNTERS.tracer.start()
    _COUNTERS.span = span
    return span


def trace_stop(_data=None):
    """Wait for the traced slice to be written; its directory and span on
    this process's clock."""
    _COUNTERS.tracer.join()
    return _COUNTERS.span
