#!/usr/bin/env python3
"""Run one benchmark cell once.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. ``--trace 0`` prints the cell's end-to-end
metrics; ``--trace 1`` traces a slice of the window on the endpoint and
prints its per-layer metrics with the trace's busy time and breakdown.
Every run checks what its window served against the plain reference and
prints each number compared beside its limit, last on standard error and
last in the result line. The last line of standard output is the result:
``{"correct", "attempted", "failed", "metrics", "device", ["breakdown"],
"checks"}``. A run whose endpoint finds no TPU, or fewer chips than the
cell asks for, exits 3 and prints no result.
"""
from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import check, harness, metrics, peaks, trace, traffic, work  # noqa: E402,E501


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def applies(metric: dict, cell: str, reported) -> bool:
    """Whether a metric of BENCHMARK.json belongs in ``cell``'s line."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") is None or metric["moves"] in reported


def end_to_end(info: dict, mix: dict) -> dict:
    win = info["window"]
    ok = [i for i, r in win.results.items() if i in win.done]
    lat = [(win.done[i] - win.due[i]) * 1e3 for i in ok]
    seconds = win.closed - win.opened
    in_window = [i for i in ok if win.done[i] <= win.closed]
    out = {"setup_s": info["setup_s"],
           "tasks_per_s": len(in_window) / seconds if seconds > 0 else None}
    if mix["loop"] == "open":
        out["latency_p50_ms"] = harness.percentile(lat, 50)
        out["latency_p95_ms"] = harness.percentile(lat, 95)
    return out


def report_window(info: dict, mix: dict) -> None:
    win = info["window"]
    late = [(win.sent[i] - win.due[i]) * 1e3 for i in win.sent]
    lat = [(win.done[i] - win.due[i]) * 1e3 for i in win.done]
    cold = sum(1 for r in win.results.values() if not r.get("warm", True))
    workers = {}
    for r in win.results.values():
        workers[r.get("worker")] = workers.get(r.get("worker"), 0) + 1
    log = harness.log
    log(f"window: {win.closed - win.opened:.3f} s, {mix['loop']} loop, "
        f"attempted={len(win.requests)} finished={len(win.results)} "
        f"failed={len(win.errors)} served_by={workers}")
    if late:
        log(f"generator lateness ms: p50={harness.percentile(late, 50):.4f} "
            f"p99={harness.percentile(late, 99):.4f} max={max(late):.4f}")
    if lat:
        log(f"latency ms (n={len(lat)}): p50={harness.percentile(lat, 50):.4f}"
            f" p95={harness.percentile(lat, 95):.4f} max={max(lat):.4f}")
    log(f"compiles in window: {info['compiles_in_window']}; cold results in "
        f"window: {cold}")
    log(f"memory_peak_bytes: {info['memory_peak_bytes']}")
    for i, err in list(win.errors.items())[:3]:
        log(f"request {i} failed: {err}")


def plain(x: float):
    """``x`` for the result line: JSON has no infinity or NaN, so those
    are written as the strings ``"inf"`` and ``"nan"``."""
    return x if math.isfinite(x) else repr(x)


def correctness(info: dict, config: dict, mix: dict, seed: int,
                limits: dict) -> tuple:
    """``(correct, checks)``: the served results' own claims, then the
    widest logit gap of a sample against the reference."""
    win = info["window"]
    platform = info["device"]["platform"]
    faults = []
    for i, r in sorted(win.results.items()):
        if r.get("platform") != platform:
            faults.append(f"request {i} served on {r.get('platform')!r}")
    if win.errors:
        faults.append(f"{len(win.errors)} request(s) never came back")
    finished, served = check.served(win, mix)
    outside = check.outside_vocab(served, int(config["vocab_size"]))
    if outside:
        faults.append(f"{outside} served token(s) outside the vocabulary "
                      f"[0, {config['vocab_size']})")
    if mix["function"] == "generate":
        for i, s in zip(finished, served):
            if len(s) != win.requests[i].n_tokens:
                faults.append(f"request {i} served {len(s)} tokens of "
                              f"{win.requests[i].n_tokens}")
    prompts, tokens, horizon = check.window_sample(win, mix, seed)
    t0 = time.perf_counter()
    gaps = check.compare(config, seed, prompts, tokens, horizon)["gaps"]
    widest = check.widest(gaps)
    n_tokens = sum(len(g) for g in gaps)
    exact = sum(int((g == 0).sum()) for g in gaps)
    harness.log(f"reference: {len(prompts)} requests, {n_tokens} served "
                f"tokens, {exact} the reference argmax, "
                f"{time.perf_counter() - t0:.3f} s")
    limit = limits["max_logit_gap"]["limit"]
    checks = {"max_logit_gap": {"value": plain(widest), "limit": limit},
              "faults": {"value": len(faults), "limit": 0}}
    for f in faults[:5]:
        harness.log(f"fault: {f}")
    return widest <= limit and not faults and n_tokens > 0, checks


def per_layer(bench: dict, cell: dict, config_entry: dict, config: dict,
              mix: dict, info: dict, reduced, device_kind: str) -> dict:
    reported = {m["name"] for m in bench["end_to_end"]
                if applies(m, cell["name"], None)}
    view = metrics.View(config=config, mix=mix, stamps=info["stamps"],
                        trace=reduced, work=work.for_config(
                            config_entry["name"]),
                        peaks=peaks.for_kind(device_kind))
    out = {}
    for m in bench["per_layer"]:
        if not applies(m, cell["name"], reported):
            continue
        value = metrics.reader(m["name"])(view)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def execute(bench: dict, cell: dict, config_entry: dict, config: dict,
            mix: dict, limits: dict, seed: int, seconds: float, trace_on: bool,
            *, started: float, require: str = "tpu",
            installer: str = harness.INSTALLER):
    """One run of ``cell``; the result line's dict. Raises
    :class:`harness.NoChip` when the endpoint's device is not ``require``."""
    info = harness.run_cell(cell, config, mix, seed, seconds, trace_on,
                            started=started, require=require,
                            installer=installer)
    report_window(info, mix)
    import jax

    jax.config.update("jax_compilation_cache_dir", harness.cache_dir())
    correct, checks = correctness(info, config, mix, seed, limits)
    dev = info["device"]
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"],
              "memory_peak_bytes": info["memory_peak_bytes"]}
    win = info["window"]
    result = {"correct": bool(correct), "attempted": len(win.requests),
              "failed": len(win.errors)}
    if trace_on:
        extracted = trace.extract(trace.find_xplane(info["log_dir"]))
        shutil.rmtree(info["log_dir"], ignore_errors=True)
        reduced = trace.reduce(extracted)
        span = info["traced"]
        harness.log(f"trace: slice {span['closed'] - span['opened']:.4f} s on "
                    f"the endpoint clock, {reduced['window_s']:.4f} s in the "
                    f"trace, device busy {reduced['busy_s']:.4f} s, steps "
                    f"{reduced['steps']}, host events {len(extracted['host'])}")
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        result["metrics"] = per_layer(bench, cell, config_entry, config, mix,
                                      info, reduced, dev["kind"])
        result["device"] = device
        result["breakdown"] = reduced["breakdown"]
    else:
        values = end_to_end(info, mix)
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in bench["end_to_end"]
            if applies(m, cell["name"], None)
            and values.get(m["name"]) is not None}
        result["device"] = device
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    args = parse(argv)
    bench = harness.load_json(ROOT / "BENCHMARK.json")
    cell, config_entry = harness.find_cell(bench, args.workload)
    config = harness.load_json(ROOT / config_entry["file"])
    mix = traffic.load_mix(cell["traffic"])
    limits = harness.load_json(harness.HERE / "checks" / f"{cell['name']}.json")
    try:
        result = execute(bench, cell, config_entry, config, mix, limits,
                         args.seed, args.seconds, bool(args.trace),
                         started=STARTED)
    except harness.NoChip as e:
        print(f"chipbench: {e}; no result", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
