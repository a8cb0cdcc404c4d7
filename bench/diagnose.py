"""Why two runs of the round-paced serving set-up disagree: a diagnosis.

Drives `ServingRuntime` the way the first benchmark attempt did: a
`DriftingZipfStream` pulled once per round inside the loop, every knob
left on ``"auto"``.  Each run reports the knobs it ended at, its replans
and the host time the stream spent generating traffic; one run with the
knobs pinned to the controller's starting values stands beside them.
Then a short profiled run records which planes, lines and event names
the profiler writes, for the trace reduction in `trace.py`.

    python bench/diagnose.py [--rounds 80] [--seed 1]   (one TPU chip)

Writes ``chiprun_out/diagnose.json``; exits non-zero without a TPU.
"""

from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
ROWS, DIM, KEYS, RATE = 9_994_943, 128, 64, 16


class TimedStream:
    """Wraps a stream and sums the host seconds its arrivals() take."""

    def __init__(self, stream):
        self.stream = stream
        self.gen_s = 0.0
        self.calls = 0

    def arrivals(self, rnd):
        t = time.perf_counter()
        out = self.stream.arrivals(rnd)
        self.gen_s += time.perf_counter() - t
        self.calls += 1
        return out


def one_run(table, seed, rounds, pinned):
    from repro.obs.telemetry import Telemetry
    from repro.serve.requests import DriftingZipfStream
    from repro.serve.runtime import ServeConfig, ServingRuntime

    knobs = dict(replan_every=4, batch_requests=16, pipeline_depth=1,
                 refresh_every=0) if pinned else {}
    bus = Telemetry()
    rt = ServingRuntime(table, ServeConfig(
        vocab=ROWS, kernel=True, keys_per_request=KEYS, summary=False,
        seed=seed, **knobs), telemetry=bus)
    stream = TimedStream(DriftingZipfStream(ROWS, keys_per_request=KEYS,
                                            arrival_rate=RATE, seed=seed))
    t = time.perf_counter()
    res = rt.run(stream, rounds)
    wall = time.perf_counter() - t
    causes = {k: v for k, v in bus.snapshot().get("counters", {}).items()
              if k.startswith("serve.replans")}
    return {"pinned": pinned, "knobs": res.knobs, "replans": res.replans,
            "replan_causes": causes, "rounds": res.rounds,
            "served": res.served, "wall_s": wall,
            "traffic_gen_s": stream.gen_s, "traffic_calls": stream.calls,
            "p50_ms": res.p50_ms, "p99_ms": res.p99_ms,
            "capacity_resizes": res.capacity_resizes,
            "ctl_events": {n: len(bus.events(n)) for n in
                           ("ctl.propose", "ctl.trial", "ctl.force",
                            "ctl.settle")}}


def trace_shape(table, seed):
    """Profile a few pinned rounds and summarise the trace's layout."""
    import jax
    from jax.profiler import ProfileData

    from repro.serve.requests import DriftingZipfStream
    from repro.serve.runtime import ServeConfig, ServingRuntime

    rt = ServingRuntime(table, ServeConfig(
        vocab=ROWS, kernel=True, keys_per_request=KEYS, summary=False,
        seed=seed, replan_every=4, batch_requests=16, pipeline_depth=1,
        refresh_every=0))
    stream = DriftingZipfStream(ROWS, keys_per_request=KEYS,
                                arrival_rate=RATE, seed=seed)
    rt.run(stream, 8)
    logdir = tempfile.mkdtemp()
    jax.profiler.start_trace(logdir)
    anchors = []
    for i in range(2):
        a = time.perf_counter_ns()
        w = time.time_ns()
        with jax.profiler.TraceAnnotation(f"bench.anchor.{i}"):
            pass
        anchors.append({"perf_ns": a, "wall_ns": w,
                        "perf_after_ns": time.perf_counter_ns()})
        if i == 0:
            rt.run(stream, 12)
    jax.profiler.stop_trace()
    files = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    out = {"files": [os.path.relpath(f, logdir) for f in files],
           "sizes": [os.path.getsize(f) for f in files],
           "anchors": anchors, "planes": []}
    pd = ProfileData.from_file(files[0])
    for plane in pd.planes:
        p = {"name": plane.name, "lines": []}
        for line in plane.lines:
            evs = list(line.events)
            names = collections.Counter(e.name for e in evs)
            p["lines"].append({
                "name": line.name, "n": len(evs),
                "top": names.most_common(25),
                "first": [[e.name, e.start_ns, e.duration_ns]
                          for e in evs[:5]],
                "anchor": [[e.name, e.start_ns, e.duration_ns]
                           for e in evs if "bench.anchor" in e.name]})
        out["planes"].append(p)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rounds", type=int, default=80)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    import jax
    if jax.default_backend() != "tpu":
        print("diagnose: needs a TPU", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, HERE)
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or os.path.join(ROOT, ".jax_cache"))
    from table import make_table
    table = make_table(ROWS, DIM, args.seed)
    runs = []
    for pinned in (False, False, False, True, True):
        r = one_run(table, args.seed, args.rounds, pinned)
        print("diagnose:", json.dumps(r), flush=True)
        runs.append(r)
    shape = trace_shape(table, args.seed)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "diagnose.json"), "w") as f:
        json.dump({"runs": runs, "trace": shape}, f, indent=1)
    print(json.dumps({"ok": True, "runs": len(runs)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
