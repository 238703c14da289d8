"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig3_latency,...] [--full]

Prints ``name,value,derived`` CSV (value µs unless the name states
otherwise). This harness measures the FaaS system itself, which runs for
real on CPU.
"""
from __future__ import annotations

import argparse
import inspect
import json
import time

from .common import ROWS

SUITES = {
    "fig3_latency": ("latency", "Fig 3 latency breakdown"),
    "fig4_scaling": ("scaling", "Fig 4 strong/weak scaling + throughput"),
    "fig5_t1_t2_data": ("data_mgmt", "Fig 5 + Tables 1-2 data management"),
    "table3_containers": ("container_cost", "Table 3 container cold starts"),
    "fig6_7_routing": ("routing", "Figs 6-7 warming-aware routing"),
    "sec7.5_batching": ("batching", "§7.5 batching"),
    "sec4.5_serialization": ("serialization",
                             "§4.5 pack-once data plane throughput"),
    "sec7.2.3_results": ("results_plane",
                         "§7.2.3 batched result plane (DESIGN.md §6)"),
    "sec7_shm": ("shm_bench",
                 "DESIGN.md §7 same-host shm vs tcp transport"),
    "sec5_executor": ("executor_bench",
                      "§5 futures-native executor submit coalescing "
                      "(DESIGN.md §8)"),
    "sec6_p2p": ("p2p_bench",
                 "§5/§6 peer data plane all-to-all shuffle "
                 "(DESIGN.md §9)"),
    "sec10_serving": ("serving_bench",
                      "DESIGN.md §10 serving fabric: jit-cache-aware "
                      "routing vs random over socket endpoints"),
    "sec5_interchange": ("interchange_bench",
                         "§5 hierarchical interchange: 100k-task burst "
                         "absorption + elastic leaves (DESIGN.md §11)"),
}

ARTIFACT = "BENCH_10.json"         # seeded from BENCH_9.json (PR 9 run)


def write_artifact(path: str, per_suite) -> None:
    """Scenario → metric map, so the perf trajectory is diffable across
    PRs (BENCH_<n>.json, n = PR number). Partial runs (``--only``,
    ``bench-smoke``) merge into an existing artifact instead of
    truncating it — only the suites that actually ran are refreshed."""
    doc = {}
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        pass
    doc.update({
        suite: {name: value for name, value, _ in rows}
        for suite, rows in per_suite.items() if rows
    })
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"# artifact written: {path}")


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--only", default="all",
                   help="comma list of suites: " + ",".join(SUITES))
    p.add_argument("--full", action="store_true",
                   help="paper-scale parameters (slower)")
    p.add_argument("--tiny", action="store_true",
                   help="smoke-test parameters (suites that support them)")
    p.add_argument("--artifact", default=ARTIFACT,
                   help="JSON artifact path ('' disables)")
    args = p.parse_args()
    sel = list(SUITES) if args.only == "all" else args.only.split(",")

    print("name,value,derived")
    t0 = time.perf_counter()
    per_suite = {}
    for key in sel:
        mod_name, desc = SUITES[key]
        print(f"# === {key}: {desc} ===", flush=True)
        mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
        t1 = time.perf_counter()
        kw = {"full": args.full}
        if args.tiny and "tiny" in inspect.signature(mod.run).parameters:
            kw["tiny"] = True
        mark = len(ROWS)
        mod.run(**kw)
        per_suite[key] = [r.split(",", 2) for r in ROWS[mark:]]
        per_suite[key] = [(n, float(v), d) for n, v, d in per_suite[key]]
        print(f"# {key} done in {time.perf_counter()-t1:.1f}s", flush=True)
    print(f"# all suites done in {time.perf_counter()-t0:.1f}s")
    if args.artifact:
        write_artifact(args.artifact, per_suite)


if __name__ == "__main__":
    main()
