#!/usr/bin/env python3
"""Chip smoke test: serve qwen1.5-0.5b at its published width on one TPU
through the path a funcX user calls.

    python chip_smoke.py

One process holds the chip and runs the whole fleet: a ``FuncXService``,
one in-process endpoint (1 manager, 1 worker) with the serving fabric
installed, and a client that drives ``serve_generate`` through
``client.executor().submit``. One cold request builds the container (init
params, compile prefill and decode), then warm requests with other
prompts and seeds follow. Every result must report ``platform == "tpu"``,
``warm`` after the first, tokens of shape ``(1, n_tokens)``, and tokens
that agree with a teacher-forced reference computed in this process
(``repro.serve.reference``).

Earlier lines print what was observed (cold build seconds, warm request
milliseconds on the host clock, peak device bytes, the compile cache);
they are informational, not metrics. The last line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Where JAX finds no TPU, the script says which platform it found and
exits non-zero; it never continues on the CPU.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

ARCH = "qwen1.5-0.5b"           # published width: 24 layers, d_model 1024
BUCKET = 64                     # prompt length: a bucket, so no padding
N_TOKENS = 8                    # within the fabric's decode horizon
N_WARM = 4
TIMEOUT_S = 900.0


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def cache_entries(path: str) -> int:
    p = Path(path)
    return sum(1 for _ in p.iterdir()) if p.is_dir() else 0


def run(device) -> None:
    import numpy as np

    from repro.compile_cache import enable_compile_cache
    from repro.configs import get_config
    from repro.core import FuncXClient, FuncXService
    from repro.serve import fabric
    from repro.serve.reference import LOGIT_TOLERANCE, TeacherForcedReference

    cache_dir = enable_compile_cache()
    print(f"compile cache: {cache_dir} entries_at_start="
          f"{cache_entries(cache_dir)}", flush=True)

    svc = FuncXService(heartbeat_timeout=5.0, shm=False)
    agent = None
    try:
        fabric.install(svc.containers)
        client = FuncXClient(svc, svc.register_user("chip-smoke"))
        eid, agent = svc.make_endpoint(client.token, "chip", n_managers=1,
                                       workers_per_manager=1)
        (fid, _), = fabric.register_zoo(client, [ARCH]).values()
        ct = fabric.jit_key(ARCH, "generate", BUCKET)
        vocab = get_config(ARCH).vocab_size
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, vocab, (1, BUCKET)).astype(np.int32)
                   for _ in range(1 + N_WARM)]

        outs, warm_ms = [], []
        ex = client.executor(endpoint_id=eid)
        try:
            for i, prompt in enumerate(prompts):
                t0 = time.perf_counter()
                out = ex.submit(fid, {"tokens": prompt, "n_tokens": N_TOKENS,
                                      "seed": i},
                                container_type=ct).result(timeout=TIMEOUT_S)
                dt = time.perf_counter() - t0
                if i == 0:
                    print(f"cold request (build + serve): {dt:.3f} s",
                          flush=True)
                else:
                    warm_ms.append(dt * 1e3)
                outs.append(out)
        finally:
            ex.shutdown(wait=False)     # a timed-out request must not hang us
        print("warm request ms (host clock): "
              + " ".join(f"{ms:.3f}" for ms in warm_ms), flush=True)
        stats = device.memory_stats() or {}
        print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}",
              flush=True)

        reference = TeacherForcedReference(ARCH)
        exact = 0
        for i, (prompt, out) in enumerate(zip(prompts, outs)):
            check(out["platform"] == "tpu",
                  f"request {i} served on {out['platform']!r}")
            check(out["warm"] == (i > 0),
                  f"request {i} warm={out['warm']}")
            check(out["arch"] == ARCH and out["bucket"] == BUCKET,
                  f"request {i} served {out['arch']} b{out['bucket']}")
            tokens = np.asarray(out["tokens"])
            check(tokens.shape == (1, N_TOKENS),
                  f"request {i} tokens shape {tokens.shape}")
            gaps = reference.gaps(prompt, tokens)
            check(bool(gaps.max() <= LOGIT_TOLERANCE),
                  f"request {i} disagrees with the reference: gaps {gaps}")
            exact += int((gaps == 0).sum())
        print(f"reference: {exact}/{len(outs) * N_TOKENS} served tokens are "
              f"the reference argmax; every gap <= {LOGIT_TOLERANCE}",
              flush=True)
        print(f"compile cache: {cache_dir} entries_at_end="
              f"{cache_entries(cache_dir)}", flush=True)
    finally:
        if agent is not None:
            agent.stop()
        svc.shutdown()


def main() -> int:
    import jax

    devices = jax.devices()
    device = devices[0]
    if device.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found {device.platform!r} "
              f"({device.device_kind}); not running on it", file=sys.stderr)
        return 2
    print(f"device: {device.platform} {device.device_kind} "
          f"count={len(devices)}", flush=True)
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    try:
        run(device)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
