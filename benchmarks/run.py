"""Benchmark harness entry point: ``python -m benchmarks.run [--quick]``.

One module per paper table/figure (DESIGN.md §8):
  fig6_overall          — Figure 6  (overall vs baselines, 5 tasks)
  fig7_scalability      — Figure 7  (2..16 nodes, + engine key-scale sweep)
  fig8_timing           — Figure 8  (adaptive action timing vs offsets)
  table2_communication  — Table 2   (communication + staleness)
  fig15_traces          — Figure 15 (per-key management traces)
  kernels_bench         — kernel micro-benches + TPU roofline bounds
  scale_sweep           — key-count scaling of the vectorized intent engine
  serve_bench           — online serving runtime vs plain lookup
                          (throughput/latency + drift adaptation +
                          double-buffered-admission overlap,
                          BENCH_serve.json)
  mesh_bench            — managed vs plain over the mesh-real shard_map
                          psum path, 8-device host mesh (re-execs itself
                          with XLA_FLAGS when needed, BENCH_mesh.json)
  hotpath_bench         — single-sort fused managed step vs the PR-4
                          three-sort/dense-grad replica, paired medians
                          (BENCH_hotpath.json; also the CI regression
                          guard via --check-baseline)

Output: ``benchmark,variant,task,metric,value`` CSV rows on stdout and in
``benchmarks/results/benchmarks.csv``.  ``--quick`` additionally writes
``BENCH_quick.json`` (per-benchmark wall-clock + headline metric) at the
repo root for the perf trajectory.  The roofline deliverable is separate
(``python -m benchmarks.roofline benchmarks/results/*.json``).
"""

from __future__ import annotations

import argparse
import json
import os
import time

_REPO_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")

# module-style aliases accepted by --only
_ALIASES = {
    "fig6_overall": "fig6",
    "fig7_scalability": "fig7",
    "fig8_timing": "fig8",
    "table2_communication": "table2",
    "fig15_traces": "fig15",
    "kernels_bench": "kernels",
    "serve_bench": "serve",
    "mesh_bench": "mesh",
    "hotpath_bench": "hotpath",
}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller workload scale (CI-sized)")
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from . import (fig6_overall, fig7_scalability, fig8_timing,
                   fig15_traces, hotpath_bench, kernels_bench, mesh_bench,
                   quality_mf, scale_sweep, serve_bench,
                   table2_communication)

    scale = 0.2 if args.quick else 0.5
    benches = {
        "fig6": lambda: fig6_overall.run(scale=scale),
        "fig7": lambda: fig7_scalability.run(
            scale=min(scale, 0.35),
            scale_keys=0 if args.quick else 100_000),
        # fig8 needs epochs >> offset for the immediate-action degradation
        # to be visible (replica lifetimes scale with the offset)
        "fig8": lambda: fig8_timing.run(scale=1.0),
        "table2": lambda: table2_communication.run(scale=scale),
        "fig15": lambda: fig15_traces.run(scale=min(scale, 0.4)),
        "kernels": lambda: kernels_bench.run(quick=args.quick),
        "quality_mf": quality_mf.run,
        "scale_sweep": lambda: scale_sweep.run(quick=args.quick),
        "serve": lambda: serve_bench.run(quick=args.quick),
        "mesh": lambda: mesh_bench.run(quick=args.quick),
        "hotpath": lambda: hotpath_bench.run(quick=args.quick),
    }
    only = None
    if args.only:
        only = {_ALIASES.get(name, name) for name in args.only.split(",")}
        unknown = only - set(benches)
        if unknown:
            ap.error(f"unknown benchmark(s): {sorted(unknown)}; "
                     f"known: {sorted(benches) + sorted(_ALIASES)}")

    all_rows = ["benchmark,variant,task,metric,value"]
    timings = {}
    for name, fn in benches.items():
        if only and name not in only:
            continue
        t0 = time.time()
        print(f"### {name} ###", flush=True)
        rows = fn()
        wall = time.time() - t0
        all_rows += rows
        timings[name] = {"wall_clock_s": round(wall, 2)}
        if rows:
            # headline metric: the benchmark's first emitted row
            _bench, variant, task, metric, value = rows[0].split(",", 4)
            timings[name]["headline"] = {
                "variant": variant, "task": task, "metric": metric,
                "value": value}
        print(f"### {name} done in {wall:.1f}s ###", flush=True)

    os.makedirs("benchmarks/results", exist_ok=True)
    with open("benchmarks/results/benchmarks.csv", "w") as f:
        f.write("\n".join(all_rows) + "\n")
    print(f"wrote {len(all_rows) - 1} rows to "
          "benchmarks/results/benchmarks.csv")
    if args.quick:
        out = os.path.join(_REPO_ROOT, "BENCH_quick.json")
        with open(out, "w") as f:
            json.dump(timings, f, indent=1)
        print(f"wrote {os.path.normpath(out)}")


if __name__ == "__main__":
    main()
