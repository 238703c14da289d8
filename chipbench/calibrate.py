#!/usr/bin/env python3
"""Readings that a cell's limit is set from: the program's widest logit
gap on many seeds, and the float8 control's on the same requests.

    python3 chipbench/calibrate.py --workload <name> --seeds 12 --first-seed <n> --seconds 6

For each seed the cell runs as the benchmark runs it (its endpoint, its
load, a short window) and a sample of what it served is kept; once every
endpoint has exited, this process runs the reference on each sample,
with the control beside it. Then the control is put in the program's
place: the tokens it ranks first replace the served tokens of the sample,
and the window goes through the benchmark's own comparison
(``run.correctness``) at the cell's limit in ``checks/<cell>.json``,
which has to read ``correct: false``. One line per seed, then the lower
reading (the program's largest) and the upper (the control's smallest).
The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import check, harness, run, traffic  # noqa: E402


def put_in_place(window, mix: dict, seed: int, tokens) -> None:
    """Replace the served tokens of the seed's sample of ``window`` with
    ``tokens`` (one array per sampled request, as :func:`check.compare`
    returns them)."""
    finished, served = check.served(window, mix)
    pick = check.sample([len(t) for t in served], seed,
                        check.SAMPLE[mix["function"]])
    key = "tokens" if mix["function"] == "generate" else "next_token"
    for j, tok in zip(pick, tokens):
        result = window.results[finished[j]]
        shape = np.asarray(result[key]).shape
        result[key] = np.asarray(tok, np.int32).reshape(shape)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--first-seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=6.0)
    args = p.parse_args(argv)
    bench = harness.load_json(ROOT / "BENCHMARK.json")
    cell, entry = harness.find_cell(bench, args.workload)
    config = harness.load_json(ROOT / entry["file"])
    mix = traffic.load_mix(cell["traffic"])
    limits = harness.load_json(harness.HERE / "checks" / f"{cell['name']}.json")
    seeds = [args.first_seed + 7919 * k for k in range(args.seeds)]
    samples = {}
    for seed in seeds:
        info = harness.run_cell(cell, config, mix, seed, args.seconds, False,
                                started=time.perf_counter())
        samples[seed] = info
    import jax

    jax.config.update("jax_compilation_cache_dir", harness.cache_dir())
    rows = []
    for seed in seeds:
        info = samples[seed]
        prompts, served, horizon = check.window_sample(info["window"], mix,
                                                       seed)
        out = check.compare(config, seed, prompts, served, horizon,
                            control=True)
        put_in_place(info["window"], mix, seed, out["control_tokens"])
        control_correct, checks = run.correctness(info, config, mix, seed,
                                                  limits)
        row = {"seed": seed, "program": check.widest(out["gaps"]),
               "control": check.widest(out["control"]),
               "control_correct": control_correct,
               "control_checks": checks,
               "tokens": int(sum(len(g) for g in out["gaps"])),
               "exact": int(sum((g == 0).sum() for g in out["gaps"])),
               "control_exact": int(sum((g == 0).sum()
                                        for g in out["control"])),
               "failed": len(info["window"].errors)}
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({
        "workload": args.workload,
        "lower": max(r["program"] for r in rows),
        "upper": min(r["control"] for r in rows),
        "program": [r["program"] for r in rows],
        "control": [r["control"] for r in rows],
        "control_correct": [r["control_correct"] for r in rows]}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
