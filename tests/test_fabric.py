"""Serving fabric (DESIGN.md §10) through the normal submit path:
``executor.submit`` → service → forwarder → endpoint → worker → a jitted
model step, at the reduced ``@smoke`` size on the CPU. This is the CPU
rehearsal of ``chip_smoke.py``, which runs the same path at the
published width on a TPU."""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.configs import get_config
from repro.serve import fabric
from repro.serve.reference import LOGIT_TOLERANCE, TeacherForcedReference

REPO = Path(__file__).resolve().parents[1]
SMOKE = "qwen1.5-0.5b@smoke"


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", SMOKE, "mamba2-370m@smoke"])
@pytest.mark.parametrize("step", fabric.STEP_KINDS)
@pytest.mark.parametrize("bucket", [16, 64])
def test_jit_key_roundtrip(arch, step, bucket):
    key = fabric.jit_key(arch, step, bucket)
    assert key == f"jit/{arch}/{step}/b{bucket}"
    assert fabric.parse_jit_key(key) == (arch, step, bucket)


@pytest.mark.parametrize("arch, n_layers, d_model, vocab", [
    ("qwen1.5-0.5b", 24, 1024, 151_936),
    (SMOKE, 2, 64, 128),
])
def test_arch_id_names_the_width(arch, n_layers, d_model, vocab):
    cfg = get_config(arch)
    assert cfg.name == arch
    assert (cfg.n_layers, cfg.d_model, cfg.vocab_size) == (n_layers, d_model, vocab)


def test_served_generate_matches_teacher_forced_reference(service, client):
    fabric.install(service.containers)
    eid, agent = service.make_endpoint(client.token, "fabric", n_managers=1,
                                       workers_per_manager=1)
    (fid, _), = fabric.register_zoo(client, [SMOKE]).values()
    bucket, n_tokens = 64, 8
    ct = fabric.jit_key(SMOKE, "generate", bucket)
    rng = np.random.default_rng(0)
    vocab = get_config(SMOKE).vocab_size
    reference = TeacherForcedReference(SMOKE)
    ex = client.executor(endpoint_id=eid)
    try:
        for i in range(3):
            prompt = rng.integers(1, vocab, (1, bucket)).astype(np.int32)
            out = ex.submit(fid, {"tokens": prompt, "n_tokens": n_tokens,
                                  "seed": i},
                            container_type=ct).result(timeout=120)
            assert out["warm"] == (i > 0)
            assert (out["arch"], out["bucket"]) == (SMOKE, bucket)
            assert out["platform"] == "cpu" and out["device_kind"]
            assert out["tokens"].shape == (1, n_tokens)
            gaps = reference.gaps(prompt, out["tokens"])
            assert gaps.max() <= LOGIT_TOLERANCE, gaps
    finally:
        ex.shutdown(wait=False)
        agent.stop()


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_served_step_reports_its_device(service, client, step):
    fabric.install(service.containers)
    eid, agent = service.make_endpoint(client.token, "fabric", n_managers=1,
                                       workers_per_manager=1)
    (fid, _), = fabric.register_zoo(client, [SMOKE], step=step).values()
    prompt = np.arange(1, 17, dtype=np.int32)[None]
    ex = client.executor(endpoint_id=eid)
    try:
        out = ex.submit(fid, {"tokens": prompt}).result(timeout=120)
    finally:
        ex.shutdown(wait=False)
        agent.stop()
    assert out["next_token"].shape == (1,) and not out["warm"]
    assert (out["arch"], out["bucket"], out["platform"]) == (SMOKE, 16, "cpu")


def test_chip_smoke_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                          capture_output=True, text=True, env=env, timeout=120)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout
