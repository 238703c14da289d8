"""Serving fabric (DESIGN.md §10) through the normal submit path:
``executor.submit`` → service → forwarder → endpoint → worker → a jitted
model step, at the reduced ``@smoke`` size on the CPU. This is the CPU
rehearsal of ``chip_smoke.py``, which runs the same path at the
published width on a TPU."""
import os
import re
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
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


@pytest.fixture(scope="module")
def served():
    """A built serving environment (both steps compiled) beside the fp32
    masters that the same seed initialises."""
    env = fabric._build_env(SMOKE, "decode", 16)
    masters = env["model"].init(jax.random.PRNGKey(fabric.PARAMS_SEED))
    batch = {"tokens": jnp.asarray(np.arange(1, 17, dtype=np.int32)[None])}
    return env, masters, batch


def test_served_weights_are_held_in_the_compute_dtype(served):
    env, masters, _ = served
    leaves = jax.tree.leaves(env["params"])
    assert len(leaves) == len(jax.tree.leaves(masters))
    assert {a.dtype for a in leaves} == {jnp.dtype(env["cfg"].dtype)}
    assert {a.dtype for a in jax.tree.leaves(masters)} == {np.dtype("float32")}


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_served_weights_give_the_masters_logits_bit_for_bit(served, step):
    """Casting once at build gives the weights the step casts from fp32
    masters on every call, so the same jitted step returns equal logits."""
    env, masters, batch = served
    outs = []
    for params in (env["params"], masters):
        logits, cache = env["prefill"](params, batch)
        if step == "decode":
            tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
            logits, _ = env["decode"](params, cache, {"tokens": tok})
        outs.append(np.asarray(logits))
    np.testing.assert_array_equal(*outs)


def _weight_casts(env, params, batch) -> int:
    """``convert`` ops in the lowered prefill step whose operand is a whole
    f32 argument of the step's entry function, i.e. a weight leaf."""
    text = env["prefill"].lower(params, batch).as_text()
    main = text.split("func.func public @main", 1)[1].split("func.func")[0]
    return len(re.findall(
        r"stablehlo\.convert %arg\d+ : \(tensor<[0-9x]*xf32>\)", main))


def test_served_step_casts_no_weight(served):
    """The mechanism engages: the served tree lowers with no per-step cast
    of a weight, where the fp32 masters cost one for each float leaf."""
    env, masters, batch = served
    assert _weight_casts(env, env["params"], batch) == 0
    assert _weight_casts(env, masters, batch) >= len(jax.tree.leaves(masters))


def test_chip_smoke_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                          capture_output=True, text=True, env=env, timeout=120)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout
