#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest offered rate with no
growing backlog. Run once, when a cell is added; the cell's traffic file
then fixes its rate as a number (0.8 of the knee).

    python3 chipbench/sweep.py --workload <name> --seed <n> --seconds 15 --rates 4 6 8 10

One endpoint, warmed once, takes one window per rate, lowest first. For
each rate it prints the requests sent and finished, the latency median and
95th percentile from when each was due, and the backlog growth: the
median latency of the last quarter of the window's requests over that of
the first quarter. A rate the system sustains reads about 1; past the knee
the queue grows all through the window and the ratio climbs.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness, traffic  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=15.0)
    p.add_argument("--rates", type=float, nargs="+", required=True)
    args = p.parse_args(argv)
    bench = harness.load_json(ROOT / "BENCHMARK.json")
    cell, entry = harness.find_cell(bench, args.workload)
    config = harness.load_json(ROOT / entry["file"])
    mix = traffic.load_mix(cell["traffic"])
    dep = harness.Cell(config, mix, args.seed, trace=False, require="tpu")
    try:
        dep.start(int(cell["chips"]))
        warm = traffic.schedule(mix, args.seed, args.seconds,
                                int(config["vocab_size"]))
        dep.warm(warm)
        for rate in sorted(args.rates):
            sched = traffic.schedule(dict(mix, rate_per_s=rate), args.seed,
                                     args.seconds, int(config["vocab_size"]))
            win = dep.drive_open(sched, args.seconds)
            order = sorted(win.done, key=lambda i: win.due[i])
            lat = np.array([(win.done[i] - win.due[i]) * 1e3 for i in order])
            q = max(len(lat) // 4, 1)
            print(json.dumps({
                "rate_per_s": rate, "sent": len(win.requests),
                "finished": len(win.results), "failed": len(win.errors),
                "p50_ms": float(np.percentile(lat, 50)),
                "p95_ms": float(np.percentile(lat, 95)),
                "growth": float(np.median(lat[-q:]) / np.median(lat[:q])),
                "drain_s": max(win.done.values()) - win.closed}), flush=True)
    finally:
        dep.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
