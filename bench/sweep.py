"""Find the knee of the open loop: the highest offered rate whose
backlog does not grow over the window.

    python3 bench/sweep.py --workload serve.dlrm-t20-10m.zipf-rate \\
        --rates 20,40,60,80 [--seconds 30] [--seed 5]   (one TPU chip)

Each rate is one run of the cell's driver with the traffic file's rate
replaced, in this one process.  Prints one JSON line per rate: the
backlog (requests due minus served) at the window's open and close, and
the latency percentiles.  The cell's traffic file then takes about four
fifths of the knee as its fixed rate.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args(argv)
    import spec
    bench = spec.load_benchmark()
    cell = spec.find(bench["workloads"], args.workload, "workload")
    config = spec.load_config(bench, cell["config"])
    traffic = spec.load_traffic(cell["traffic"])
    import jax
    if jax.default_backend() != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    run.enable_compile_cache()
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    import readers
    driver = spec.load_driver(config["kind"])
    peaks = spec.peaks(jax.devices()[0].device_kind)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        t = dict(traffic, rate_rps=rate, drain_limit_s=5)
        ctx, checks, att, failed, _ = driver.run_cell(
            config, t, args.seed + i, args.seconds, False,
            time.perf_counter_ns(), peaks, int(cell["chips"]))
        print(json.dumps({
            "rate_rps": rate, "attempted": att, "unserved": failed,
            "p50_ms": readers.percentile_ms(ctx, 50),
            "p95_ms": readers.percentile_ms(ctx, 95),
            "served_per_s": (readers.lookups_per_s(ctx) or 0)
            / ctx.keys_per_request, **ctx.info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
