"""The whole run at toy size on the CPU: an endpoint process, the load,
the reference. The command refuses to report off the TPU; the rehearsal,
which skips that look, is correct; and with the timed path broken
underneath it, one fault at a time, it is not."""
import json
import os
import subprocess
import sys
import time

import pytest

from chipbench import run
from chipbench.harness import ROOT, find_cell
from chipbench.tests import smoke

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = {
    "qwen05b-gen-poisson": (smoke.QWEN, smoke.GEN),
    "qwen05b-prefill-closed64": (smoke.QWEN, smoke.PREFILL),
}


def rehearse(name, installer="chipbench.remote:install", trace=False):
    """The cell at toy size. The open-loop cell is not in BENCHMARK.json
    (PERF.md, Open questions); the harness's open-loop path, which a later
    cell brings data for, is rehearsed all the same."""
    config, mix = CELLS[name]
    try:
        cell, entry = find_cell(BENCH, name)
    except KeyError:
        cell, entry = {"name": name, "chips": 1}, None
    limits = {"max_logit_gap": {"limit": smoke.LIMIT}}
    return run.execute(BENCH, cell, entry, config, mix, limits, 2 ** 31 + 9,
                       3, trace, started=time.perf_counter(), require="cpu",
                       installer=installer)


def test_the_command_refuses_to_report_off_the_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "qwen05b-prefill-closed64", "--seed", "5", "--seconds", "2",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert out.returncode == 3, out.stderr[-2000:]
    assert "no result" in out.stderr
    assert not any(line.startswith("{") for line in out.stdout.splitlines())


@pytest.mark.parametrize("name", sorted(CELLS))
def test_rehearsal_is_correct(name, capsys):
    res = rehearse(name)
    assert "manager_timeout 120.0 s" in capsys.readouterr().out
    assert res["correct"], res
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "checks"
    e2e = {m["name"] for m in BENCH["end_to_end"]
           if name in m.get("workloads", [name])}
    assert set(res["metrics"]) == e2e


def test_traced_rehearsal_reads_the_stamp_layers(monkeypatch):
    from chipbench import peaks
    monkeypatch.setattr(peaks, "for_kind", lambda kind: {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    res = rehearse("qwen05b-prefill-closed64", trace=True)
    assert res["correct"]
    # the CPU has no device plane: only the stamp layer has something
    assert set(res["metrics"]) == {"dispatch_ms.prefill"}
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert "breakdown" in res


@pytest.mark.parametrize("name,fault", [
    ("qwen05b-gen-poisson", "token"),
    ("qwen05b-gen-poisson", "frozen_state"),
    ("qwen05b-prefill-closed64", "answer"),
])
def test_a_broken_timed_path_is_not_correct(name, fault):
    res = rehearse(name, installer=f"chipbench.tests.faulty:{fault}")
    assert res["correct"] is False
    check = res["checks"]["max_logit_gap"]
    assert check["value"] > check["limit"]


@pytest.mark.parametrize("name", sorted(CELLS))
def test_a_token_on_a_padded_row_is_not_correct(name):
    """A served id past the vocabulary has no reference logit: the gap
    reads infinite (written ``"inf"``, which JSON can carry) and the
    token is counted as a fault."""
    res = rehearse(name, installer="chipbench.tests.faulty:padded_row")
    assert res["correct"] is False
    assert res["checks"]["max_logit_gap"]["value"] == "inf"
    assert res["checks"]["faults"]["value"] >= 1
    json.loads(json.dumps(res, allow_nan=False))


def test_the_installer_serves_the_published_preset_fields():
    from chipbench import remote
    from repro.configs import get_config

    class Fabric:
        pass

    fabric = Fabric()
    fabric.get_config = get_config
    remote.set_preset(fabric, "qwen1.5-0.5b@smoke",
                       {"rope_theta": 1000000.0})
    assert fabric.get_config("qwen1.5-0.5b@smoke").rope_theta == 1000000.0
    assert fabric.get_config("qwen1.5-0.5b@smoke").d_model == 64
    assert fabric.get_config("mamba2-370m@smoke") == get_config(
        "mamba2-370m@smoke")
    with pytest.raises(RuntimeError, match="no get_config"):
        remote.set_preset(Fabric(), "qwen1.5-0.5b", {"rope_theta": 1.0})
    fabric.get_config = get_config
    with pytest.raises(TypeError):
        remote.set_preset(fabric, "qwen1.5-0.5b", {"no_such_field": 1})


@pytest.mark.parametrize("name", sorted(CELLS))
def test_the_control_in_the_programs_place_is_not_correct(name):
    """What ``calibrate.py`` does on the chip: the float8 control's tokens
    replace the window's sampled tokens, and the run's own comparison at
    the cell's limit reads ``correct: false``."""
    from chipbench import calibrate, check, harness

    config, mix = CELLS[name]
    seed = 2 ** 31 + 11
    info = harness.run_cell({"name": name, "chips": 1}, config, mix, seed, 3,
                            False, started=time.perf_counter(),
                            require="cpu")
    limits = {"max_logit_gap": {"limit": smoke.LIMIT}}
    assert run.correctness(info, config, mix, seed, limits)[0]
    prompts, served, horizon = check.window_sample(info["window"], mix, seed)
    out = check.compare(config, seed, prompts, served, horizon, control=True)
    calibrate.put_in_place(info["window"], mix, seed, out["control_tokens"])
    correct, checks = run.correctness(info, config, mix, seed, limits)
    assert correct is False
    assert checks["max_logit_gap"]["value"] > smoke.LIMIT
    if mix["function"] == "prefill":
        # one token a request: the comparison reads what the control read
        assert checks["max_logit_gap"]["value"] == pytest.approx(
            check.widest(out["control"]))
