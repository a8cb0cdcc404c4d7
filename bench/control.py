"""Readings that set a cell's limits: the program and its control.

    python3 bench/control.py --workload <cell> --seeds 101,102,... \\
        --control-seeds 201,202,203 [--seconds 10]       (on the chip)

Runs the cell's driver in this one process (set-up is long, so one
process reads every seed): first the program as the configuration
states it, on each of ``--seeds``; then the control, the same program
serving the table in bfloat16 (its own lower-precision path), on each of
``--control-seeds``.  Prints one JSON line per run with every number
compared.  The program's largest readings are the lower ones, the
control's smallest the upper ones; `PERF.md` records both and the limit
set between them.  The benchmark's own runs never run this.
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
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    import spec
    bench = spec.load_benchmark()
    cell = spec.find(bench["workloads"], args.workload, "workload")
    config = spec.load_config(bench, cell["config"])
    traffic = spec.load_traffic(cell["traffic"])
    import jax
    if jax.default_backend() != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    run.enable_compile_cache()
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    driver = spec.load_driver(config["kind"])
    peaks = spec.peaks(jax.devices()[0].device_kind)
    plan = [(int(s), None) for s in args.seeds.split(",") if s] + \
        [(int(s), "bfloat16") for s in args.control_seeds.split(",") if s]
    for seed, dtype in plan:
        _, checks, att, failed, _ = driver.run_cell(
            config, traffic, seed, args.seconds, False,
            time.perf_counter_ns(), peaks, int(cell["chips"]), dtype=dtype)
        print(json.dumps({"seed": seed, "control": dtype is not None,
                          "correct": spec.is_correct(checks),
                          "attempted": att, "failed": failed,
                          "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
