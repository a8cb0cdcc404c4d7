"""Profiler trace of the window, and its reduction to device numbers.

`Capture` starts JAX's profiler at the window's open and stops it at the
close.  At each end it emits an anchor `jax.profiler.TraceAnnotation` at
a known `time.perf_counter_ns`, the clock of the program's `SpanTracer`;
the anchors put the profiler's clock and the spans' clock on one line.

`load_events` flattens the ``.xplane.pb`` into ``[plane, line, name,
start_ns, dur_ns]`` rows, and `reduce_trace` turns those rows into the
device's busy seconds, the time per device operation, and the idle gaps,
each gap named by the program span the host was in while the device had
nothing to run.
"""

from __future__ import annotations

import glob
import os
import shutil
import tempfile
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

OPEN, CLOSE = "bench.open", "bench.close"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
# spans that wrap the others: a gap is named by what ran inside them (a
# replan's gap by its part: serve.plan.probe_view, serve.plan.solve, ...)
ENVELOPES = frozenset({"serve.round", "serve.request", "bench.segment",
                       "serve.plan"})
NO_SPAN = "outside_program_spans"


def anchor(name: str) -> int:
    """Emit a trace annotation; return its `perf_counter_ns` midpoint."""
    import jax
    a = time.perf_counter_ns()
    with jax.profiler.TraceAnnotation(name):
        pass
    return (a + time.perf_counter_ns()) // 2


class Capture:
    """One profiled window; the trace lives in a temporary directory
    that `load` removes."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.anchors: Dict[str, int] = {}

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.anchors[OPEN] = anchor(OPEN)

    def stop(self) -> None:
        import jax
        self.anchors[CLOSE] = anchor(CLOSE)
        jax.profiler.stop_trace()

    def load(self) -> List[list]:
        try:
            files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if len(files) != 1:
                raise RuntimeError(f"expected one xplane file, found "
                                   f"{len(files)}")
            return load_events(files[0])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def op_name(text: str) -> str:
    """An operation's name from the profiler's event name, which on the
    TPU is the whole HLO instruction (``%pad.4 = f32[...] pad(...)``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def load_events(path: str) -> List[list]:
    """Device operations and the anchors, as flat rows."""
    from jax.profiler import ProfileData
    rows = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for e in line.events:
                if device or e.name in (OPEN, CLOSE):
                    rows.append([plane.name, line.name, op_name(e.name),
                                 int(e.start_ns), int(e.duration_ns)])
    return rows


def _union(iv: np.ndarray) -> np.ndarray:
    """Merge [start, end) intervals (sorted by start) into disjoint ones."""
    if len(iv) == 0:
        return iv.reshape(0, 2)
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, np.int64)


def clock_offset(rows: Sequence[list], anchors: Dict[str, int]) -> int:
    """Trace time minus `perf_counter_ns`, from the two anchors."""
    seen = {r[2]: r[3] for r in rows if r[2] in (OPEN, CLOSE)}
    missing = {OPEN, CLOSE} - set(seen)
    if missing:
        raise RuntimeError(f"anchors missing from the trace: {missing}")
    return int(round(np.mean([seen[k] - anchors[k] for k in (OPEN, CLOSE)])))


def label_gap(t0: int, t1: int, spans: Sequence[tuple]) -> str:
    """The program span (not an envelope) that overlaps [t0, t1) most."""
    best, best_ov = NO_SPAN, 0
    for name, s0, s1 in spans:
        if name in ENVELOPES or s1 <= t0 or s0 >= t1:
            continue
        ov = min(s1, t1) - max(s0, t0)
        if ov > best_ov:
            best, best_ov = name, ov
    return best


def gap_cover(t0: int, t1: int, spans: Sequence[tuple]) -> Dict[str, float]:
    """Milliseconds of [t0, t1) under each program span name (envelopes
    left out), and under none of them (``NO_SPAN``)."""
    out: Dict[str, float] = {}
    clipped = []
    for name, s0, s1 in spans:
        if name in ENVELOPES or s1 <= t0 or s0 >= t1:
            continue
        a, b = max(s0, t0), min(s1, t1)
        out[name] = out.get(name, 0.0) + (b - a) / 1e6
        clipped.append((a, b))
    covered = _union(np.asarray(sorted(clipped), np.int64).reshape(-1, 2))
    out[NO_SPAN] = (t1 - t0 - int((covered[:, 1] - covered[:, 0]).sum())) \
        / 1e6
    return out


def reduce_trace(rows: Sequence[list], anchors: Dict[str, int],
                 spans: Sequence[tuple] = (), top: int = 10) -> dict:
    """Busy and idle time of the device between the anchors.

    ``spans`` are ``(name, t0_ns, t1_ns)`` on the `perf_counter_ns`
    clock.  Returns ``busy_s`` and ``window_s`` (busy averaged over the
    device planes), ``op_s`` (seconds per operation name, summed over
    devices), ``device_ops`` (the ``top`` heaviest) and ``idle_gaps`` (the
    ``top`` longest gaps of the first device, each named by `label_gap`)."""
    off = clock_offset(rows, anchors)
    w0, w1 = anchors[OPEN] + off, anchors[CLOSE] + off
    by_dev: Dict[str, list] = {}
    op_s: Dict[str, float] = {}
    for plane, _, name, s, d in rows:
        if not plane.startswith(DEVICE_PREFIX):
            continue
        s0, s1 = max(s, w0), min(s + d, w1)
        if s1 <= s0:
            continue
        by_dev.setdefault(plane, []).append((s0, s1))
        op_s[name] = op_s.get(name, 0.0) + (s1 - s0) / 1e9
    if not by_dev:
        raise RuntimeError("no device operation ran in the traced window")
    busy, first_gaps = [], None
    for plane in sorted(by_dev):
        u = _union(np.asarray(sorted(by_dev[plane]), np.int64))
        busy.append(float((u[:, 1] - u[:, 0]).sum()) / 1e9)
        if first_gaps is None:
            edges = np.concatenate([[w0], u.ravel(), [w1]]).reshape(-1, 2)
            first_gaps = edges[edges[:, 1] > edges[:, 0]]
    order = np.argsort(first_gaps[:, 0] - first_gaps[:, 1],
                       kind="stable")[:top]
    pspans = [(n, a + off, b + off) for n, a, b in spans]
    gaps = [[label_gap(int(first_gaps[i, 0]), int(first_gaps[i, 1]), pspans),
             float(first_gaps[i, 1] - first_gaps[i, 0]) / 1e9]
            for i in order]
    ops = sorted(op_s.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": float(np.mean(busy)), "window_s": (w1 - w0) / 1e9,
            "op_s": op_s, "device_ops": [[k, v] for k, v in ops],
            "idle_gaps": gaps, "devices": len(by_dev)}


def op_seconds(summary: Optional[dict], needles: Sequence[str]) -> float:
    """Device seconds of operations whose name holds any of ``needles``."""
    if not summary:
        return 0.0
    return sum(v for k, v in summary["op_s"].items()
               if any(n in k for n in needles))
