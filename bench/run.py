#!/usr/bin/env python3
"""Run one cell of the benchmark on the accelerator and print its line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1> [--control 1]

Run from the root of a checkout, one process per chip set.  The cell,
its configuration, its traffic mix, its metrics and the driver of its
configuration's kind are found by name from ``BENCHMARK.json`` (see
`spec.py`).  Set-up makes the table and the
traffic from ``--seed``, warms the runtime up on the cell's own traffic,
then measures ``--seconds``.  ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics, read from a
profiler trace of the window and the program's spans and counters.

After the window every served row is compared with the plain reference
(`table.reference_rows`).  ``--control 1`` serves the table in bfloat16,
the program's own lower-precision path, and must come out not correct.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared beside its
limit).  Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START_NS = time.perf_counter_ns()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout (or
    where ``JAX_COMPILATION_CACHE_DIR`` says), for every program size."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def result_line(ctx, checks, attempted, failed, mem_peak, bench, cell,
                traced, device) -> dict:
    import spec
    out = {"correct": spec.is_correct(checks),
           "attempted": attempted, "failed": failed,
           "metrics": spec.read_metrics(bench, cell, traced, ctx),
           "device": dict(device, memory_peak_bytes=mem_peak)}
    if traced:
        out["device"]["busy_s"] = ctx.trace["busy_s"]
        out["device"]["window_s"] = ctx.trace["window_s"]
        out["breakdown"] = {"device_ops": ctx.trace["device_ops"],
                            "idle_gaps": ctx.trace["idle_gaps"]}
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="1: serve the table in bfloat16 (must fail)")
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    import spec
    bench = spec.load_benchmark()
    cell = spec.find(bench["workloads"], args.workload, "workload")
    config = spec.load_config(bench, cell["config"])
    traffic = spec.load_traffic(cell["traffic"])

    import jax
    devices = jax.devices()
    if jax.default_backend() != "tpu" or len(devices) < cell["chips"]:
        print(f"bench: cell {args.workload} needs {cell['chips']} TPU "
              f"chip(s); JAX found {len(devices)} {jax.default_backend()}"
              f" device(s)", file=sys.stderr)
        return 2
    enable_compile_cache()
    sys.path.insert(0, os.path.join(ROOT, "src"))
    dev = devices[0]
    peaks = spec.peaks(dev.device_kind)
    driver = spec.load_driver(config["kind"])
    ctx, checks, attempted, failed, mem_peak = driver.run_cell(
        config, traffic, args.seed, args.seconds, bool(args.trace),
        T_START_NS, peaks, int(cell["chips"]),
        dtype="bfloat16" if args.control else None)
    out = result_line(ctx, checks, attempted, failed, mem_peak, bench,
                      args.workload, bool(args.trace),
                      {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(devices)})
    for k, v in ctx.info.items():
        print(f"bench: {k} {v}", file=sys.stderr)
    for k, c in out["checks"].items():
        print(f"check {k} {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
