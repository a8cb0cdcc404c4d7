"""Drive the serving runtime through one cell: set-up, window, check.

The runtime is built once, primed with a burst, warmed up on the cell's
own traffic, and then measured: the same `ServingRuntime` object serves
every phase, through its public `run` in short segments of rounds
(``2 * replan_every``), so the harness can open and close the window on
the clock between segments.  Knobs that follow a clock are pinned by the
configuration and checked at the end of every segment.

Times come from the program's own spans (`SpanTracer`, injected): a
request's ``serve.request`` span ends when its batch was blocked.
Latency runs from the request's due time to that end.

After the window no new request is released, and the runtime serves
what it holds, for up to the mix's ``drain_limit_s``: every request
released in the run has then been served, or it counts as unserved.
A closed loop that outlasts its pool of keys recycles it, and each
release is compared with the reference of the pool row it took.
On more than one chip the table is built row-sharded over the cell's
chips, as the program's mesh collective places it.
"""

from __future__ import annotations

import gc
import math
import resource
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from table import make_table, reference_rows
from devtrace import CLOSE, OPEN, Capture, gap_cover, reduce_trace
from traffic import ClosedLoop, OpenLoop, Requests, exponential_gaps, \
    request_keys

PINNED = ("cache_capacity", "replan_every", "batch_requests",
          "pipeline_depth", "refresh_every")
SPAN_CAPACITY = 1 << 21
DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def moved_knobs(pinned: Dict[str, object], knobs: Dict[str, object]
                ) -> Dict[str, object]:
    """The pinned knobs whose end-of-run value differs from the pin."""
    return {k: knobs.get(k) for k, v in pinned.items() if knobs.get(k) != v}


COUNTED = ("serve.replans", "serve.refreshes", "serve.capacity_resizes",
           "serve.overflow_batches", "serve.requeues", "serve.stage_topups")


def window_counts(log: List[tuple], t0: int, t1: int) -> Dict[str, float]:
    """Totals of the runtime's unlabelled counters over [t0, t1)."""
    out = {f"window_{n.split('.', 1)[1]}": 0.0 for n in COUNTED}
    for t, n, v in log:
        if n in COUNTED and t0 <= t < t1:
            out[f"window_{n.split('.', 1)[1]}"] += v
    return out


def recording_bus():
    """A `Telemetry` bus that also logs every unlabelled counter and
    gauge write with its `perf_counter_ns` time."""
    from repro.obs.telemetry import Telemetry

    class RecordingBus(Telemetry):
        def __init__(self):
            super().__init__()
            self.log: List[tuple] = []

        def inc(self, name, n=1, **labels):
            super().inc(name, n, **labels)
            if not labels:
                self.log.append((time.perf_counter_ns(), name, float(n)))

        def set(self, name, v, **labels):
            super().set(name, v, **labels)
            if not labels:
                self.log.append((time.perf_counter_ns(), name, float(v)))

    return RecordingBus()


class Burst:
    """Stream adapter that hands out requests [0, n) at its first call."""

    def __init__(self, requests: Requests, n: int):
        self.r, self.n, self.done = requests, n, False

    def arrivals(self, rnd):
        if self.done:
            return []
        self.done = True
        now = time.perf_counter_ns()
        return [self.r.make(i, now) for i in range(self.n)]


class Quiet:
    """Stream adapter that releases nothing: the runtime serves what it
    holds."""

    def arrivals(self, rnd):
        return []


class CompileLog:
    """Times of backend compiles and persistent-cache reads."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax
        self.at: List[int] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name in self.EVENTS:
            self.at.append(time.perf_counter_ns())

    def count(self, t0: int, t1: int) -> int:
        return sum(t0 <= t < t1 for t in self.at)


class HostUsage:
    """The process's CPU time, page faults, context switches and longest
    collector pause between two marks: what the host did in the window."""

    def __init__(self):
        self.marks: Dict[str, tuple] = {}
        self.pauses: List[tuple] = []         # (start_ns, ms)
        self._t0 = 0
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        now = time.perf_counter_ns()
        if phase == "start":
            self._t0 = now
        else:
            self.pauses.append((self._t0, (now - self._t0) / 1e6))

    def mark(self, name: str) -> None:
        if name not in self.marks:
            self.marks[name] = (time.perf_counter_ns(), time.process_time(),
                                resource.getrusage(resource.RUSAGE_SELF))

    def report(self) -> Dict[str, float]:
        gc.callbacks.remove(self._on_gc)
        (t0, c0, r0), (t1, c1, r1) = self.marks["open"], self.marks["close"]
        return {
            "window_cpu_s_per_s": (c1 - c0) / ((t1 - t0) / 1e9),
            "window_minor_faults": r1.ru_minflt - r0.ru_minflt,
            "window_major_faults": r1.ru_majflt - r0.ru_majflt,
            "window_involuntary_switches": r1.ru_nivcsw - r0.ru_nivcsw,
            "window_gc_pause_max_ms": max(
                (ms for t, ms in self.pauses if t0 <= t < t1), default=0.0)}


@dataclass
class Ctx:
    """What the metric readers read (see ``bench/metrics``)."""

    kind: str
    setup_s: float
    window_ns: tuple                 # the end-to-end window
    layer_window_ns: tuple           # the traced window (or the above)
    due_ns: np.ndarray               # requests of the window
    enq_ns: np.ndarray
    served_ns: np.ndarray            # -1: not served
    keys_per_request: int
    tokens_per_batch: int
    row_bytes: int
    spans: List[tuple]               # (name, t0_ns, t1_ns, a, b)
    bus_log: List[tuple]             # (t_ns, name, value)
    peaks: dict
    trace: Optional[dict] = None
    info: Dict[str, float] = field(default_factory=dict)

    def window_spans(self, name: str) -> List[tuple]:
        w0, w1 = self.layer_window_ns
        return [s for s in self.spans if s[0] == name and w0 <= s[1] < w1]

    def window_log(self, name: str) -> List[float]:
        w0, w1 = self.layer_window_ns
        return [v for t, n, v in self.bus_log if n == name and w0 <= t < w1]


def serve_config(config: dict, traffic: dict, seed: int, chips: int = 1):
    from repro.serve.runtime import ServeConfig
    knobs = dict(config["serve"])
    if chips > 1:
        if knobs.get("collective") != "mesh":
            raise ValueError(f"{config['name']}: a table on {chips} chips "
                             f"needs serve.collective 'mesh'")
        knobs["model_shards"] = chips
    return ServeConfig(vocab=int(config["num_embeddings"]),
                       keys_per_request=int(traffic["keys_per_request"]),
                       summary=False, seed=seed, **knobs)


def table_sharding(chips: int):
    """Where the table is built: one chip, or row shards over ``chips``
    as the program's `MeshBackend.place_table` would place it."""
    if chips <= 1:
        return None
    from repro.launch.mesh import make_model_mesh
    from repro.launch.sharding import managed_table_sharding
    return managed_table_sharding(make_model_mesh(chips), "model")


def served_total(bus) -> int:
    return int(bus.counter_value("serve.requests", tenant="default"))


def run_cell(config: dict, traffic: dict, seed: int, seconds: float,
             traced: bool, t_start_ns: int, peaks: dict, chips: int = 1,
             dtype: Optional[str] = None) -> tuple:
    """One run of a serving cell on ``chips`` chips.  Returns (ctx,
    checks, attempted, failed, memory_peak_bytes)."""
    import jax

    from repro.obs.trace import SpanTracer
    from repro.serve.runtime import ServingRuntime

    rows, dim = int(config["num_embeddings"]), int(config["embedding_dim"])
    dtype = dtype or config["dtype"]
    closed = traffic["kind"] == "serve_closed"
    warm_ns = int(float(traffic["warmup_s"]) * 1e9)
    win_ns = int(seconds * 1e9)
    prime = int(traffic["prime_requests"])
    compiles = CompileLog()

    # ---- set-up: table, traffic, runtime, prime, warm-up
    t_setup = time.perf_counter_ns()
    table = make_table(rows, dim, seed, dtype, table_sharding(chips))
    t_table = time.perf_counter_ns()
    if closed:
        n_open = 0
        total = prime + int(traffic["pool_requests"])
    else:
        rate = float(traffic["rate_rps"])
        horizon = float(traffic["warmup_s"]) + seconds \
            + float(traffic["drain_limit_s"])
        n_open = int(math.ceil(rate * horizon))
        offsets = np.cumsum(exponential_gaps(rate, n_open, seed))
        total = prime + n_open
    keys = request_keys(rows, traffic, total, seed)
    reqs = Requests(keys, pool_from=prime)
    t_traffic = time.perf_counter_ns()
    bus = recording_bus()
    tracer = SpanTracer(capacity=SPAN_CAPACITY)
    scfg = serve_config(config, traffic, seed, chips)
    rt = ServingRuntime(table, scfg, telemetry=bus, tracer=tracer)
    seg = 2 * int(scfg.replan_every)
    pinned = {k: config["serve"][k] for k in PINNED}
    moved: Dict[str, object] = {}
    zero_served = 0
    outputs: Dict[int, np.ndarray] = {}

    def segment(stream, keep) -> None:
        nonlocal zero_served
        t = time.perf_counter_ns()
        res = rt.run(stream, seg, warmup_backlog=0, collect_outputs=True)
        tracer.record("bench.segment", t, time.perf_counter_ns())
        zero_served += res.zero_served
        moved.update(moved_knobs(pinned, res.knobs))
        for rid, out in res.outputs.items():
            if keep(rid):
                outputs[rid] = out

    burst = Burst(reqs, prime)
    for _ in range(50):     # a lost request shows in the window, not here
        segment(burst, lambda rid: False)
        if served_total(bus) >= prime:
            break

    # set-up's objects (traffic, runtime, compiled programs) stay alive for
    # the whole run: keep the collector's full passes off them
    gc.collect()
    gc.freeze()
    origin = time.perf_counter_ns()
    phases = {"setup_table_s": (t_table - t_setup) / 1e9,
              "setup_traffic_s": (t_traffic - t_table) / 1e9,
              "setup_runtime_and_prime_s": (origin - t_traffic) / 1e9}
    w0, w1 = origin + warm_ns, origin + warm_ns + win_ns
    if closed:
        stream = ClosedLoop(reqs, int(traffic["outstanding"]), bus)
    else:
        stream = OpenLoop(reqs, offsets, first=prime)
        stream.start(origin)
        lo = prime + int(np.searchsorted(offsets, warm_ns / 1e9, "left"))
        hi = prime + int(np.searchsorted(offsets, (warm_ns + win_ns) / 1e9,
                                         "left"))
    drain_ns = int(float(traffic.get("drain_limit_s", 0)) * 1e9)
    capture = Capture() if traced else None
    usage = HostUsage()
    opened = closed_at = None
    while True:
        now = time.perf_counter_ns()
        if now >= w0:
            usage.mark("open")
        if now >= w1:
            usage.mark("close")
        if capture is not None and opened is None and now >= w0:
            capture.start()
            opened = capture.anchors[OPEN]
        if capture is not None and opened is not None \
                and closed_at is None and now >= w1:
            capture.stop()
            closed_at = capture.anchors[CLOSE]
        if now >= w1:
            if closed:
                break
            if sum(1 for r in range(lo, hi) if r in outputs) == hi - lo \
                    or now >= w1 + drain_ns:
                break
        segment(stream, (lambda rid: time.perf_counter_ns() >= w0)
                if closed else (lambda rid: lo <= rid < hi))
    if capture is not None and closed_at is None:
        capture.stop()
        closed_at = capture.anchors[CLOSE]
    if closed:
        # release nothing more; serve what the runtime holds, so that a
        # request still missing after this was lost, not late
        quiet = Quiet()
        while served_total(bus) < reqs.issued \
                and time.perf_counter_ns() < w1 + drain_ns:
            segment(quiet, lambda rid: True)

    # ---- after the window: device memory, spans, then the reference
    stats = [d.memory_stats() or {} for d in jax.devices()]
    mem_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    events = tracer.events()
    if tracer.dropped:
        raise RuntimeError(f"span ring dropped {tracer.dropped} spans")
    spans = [(e["name"], e["t0_ns"], e["t1_ns"], e["a"], e["b"])
             for e in events]
    served_at = {a: t1 for n, _, t1, a, _ in spans if n == "serve.request"}
    backlog = {}
    if not closed:
        due_all = origin + stream.offsets_ns
        done = np.sort(np.fromiter(
            (t for a, t in served_at.items() if a >= prime), np.int64))
        for tag, t in (("backlog_at_open", w0), ("backlog_at_close", w1)):
            backlog[tag] = int(np.searchsorted(due_all, t, "right")
                               - np.searchsorted(done, t, "right"))
    if closed:
        # every request released in the run; the window's are those
        # served inside it, and all served from its open on are compared
        every = np.arange(reqs.issued)
        at = np.array([served_at.get(int(r), -1) for r in every], np.int64)
        unserved = int(np.count_nonzero(at < 0))
        keep = (at >= w0) & (at < w1)
        rids, served_ns = every[keep], at[keep]
        compared = every[at >= w0]
    else:
        rids = np.arange(lo, hi)
        served_ns = np.array([served_at.get(int(r), -1) for r in rids],
                             np.int64)
        unserved = int(np.count_nonzero(served_ns < 0))
        compared = rids[served_ns >= 0]
    stamps = np.unique(served_ns[served_ns >= 0])
    stall = {"longest_gap_between_served_batches_ms": 0.0}
    if stamps.size > 1:
        i = int(np.argmax(np.diff(stamps)))
        g0, g1 = int(stamps[i]), int(stamps[i + 1])
        cover = gap_cover(g0, g1, [(n, a, b) for n, a, b, _, _ in spans])
        stall = {"longest_gap_between_served_batches_ms": (g1 - g0) / 1e6,
                 "longest_gap_at_s": (g0 - w0) / 1e9,
                 "longest_gap_spans_ms": ",".join(
                     f"{n}:{ms:.1f}" for n, ms in sorted(
                         cover.items(), key=lambda kv: -kv[1])[:4])}
    due_ns = reqs.due_ns[rids]
    enq_ns = reqs.enqueue_ns(rids)
    cycles = {"pool_cycles": stream.pool_cycles} if closed else {}
    del rt, table, burst, stream
    gc.unfreeze()
    gc.collect()        # the runtime holds cycles; free the table now

    trace = None
    if capture is not None:
        trace = reduce_trace(capture.load(), capture.anchors,
                             [(s[0], s[1], s[2]) for s in spans])
    wrong, gap = 0, 0.0
    for rid in compared:
        got = outputs.get(int(rid))
        if got is None:
            wrong += 1
            continue
        want = reference_rows(keys[reqs.row_of(int(rid))], dim, seed)
        d = float(np.max(np.abs(np.asarray(got, np.float32) - want)))
        gap = max(gap, d)
        wrong += d > 0
    checks = {
        "row_gap_max": (gap, 0.0),
        "requests_wrong": (wrong, 0),
        "requests_unserved": (unserved, 0),
        "zero_served": (zero_served, 0),
        "knobs_moved": (len(moved), 0),
    }
    ctx = Ctx(kind="closed" if closed else "open",
              setup_s=(w0 - t_start_ns) / 1e9, window_ns=(w0, w1),
              layer_window_ns=((opened, closed_at) if traced
                               else (w0, w1)),
              due_ns=due_ns, enq_ns=enq_ns, served_ns=served_ns,
              keys_per_request=int(traffic["keys_per_request"]),
              tokens_per_batch=int(scfg.batch_requests)
              * int(traffic["keys_per_request"]),
              row_bytes=dim * DTYPE_BYTES[dtype],
              spans=spans, bus_log=bus.log, peaks=peaks, trace=trace,
              info={**phases, **backlog, **cycles,
                    **window_counts(bus.log, w0, w1),
                    "compiles_in_window": compiles.count(w0, w1),
                    **stall, **usage.report(),
                    "requests_in_window": int(rids.size),
                    "requests_compared": int(compared.size),
                    "knobs_moved": str(moved) if moved else "none"})
    return ctx, checks, int(compared.size) + unserved, wrong + unserved, \
        mem_peak
