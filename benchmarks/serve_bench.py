"""Serving benchmark: the intent-signaled online runtime vs plain lookup.

Measures end-to-end request throughput and p50/p99 latency of the
managed serving runtime (`repro.serve`) against the unmanaged
vocab-parallel baseline across Zipf skews and hot-set drift rates, plus
a drift-adaptation section and a zero-tuning section that check the
acceptance invariants:

  (a) managed serving >= 1.5x plain-lookup throughput at Zipf skew >= 1.0;
  (b) after a hot-set rotation the miss rate returns to within 2x of the
      pre-rotation steady state within one replan round;
  (c) zero silently-dropped (zero-served) requests across the run;
  (d) the online controller, starting from UNTUNED defaults
      (capacity at the ladder floor, short cadence), reaches >= 0.9x the
      frozen hand-tuned managed throughput within a single bench run at
      every measured skew — with zero zero-served tokens across every
      mid-run capacity resize.

The operating config carries NO hand-set runtime knobs: capacity, replan
cadence, refresh cadence and double-buffered admission are all ``"auto"``
(DESIGN.md §13).  The PR-6 hand-tuned values survive only as the frozen
``HAND_TUNED`` reference arm that the auto section compares against —
the serving analogue of the hotpath bench's frozen legacy replica.

Cost model: the embedding is vocab-sharded ``N_SHARDS`` ways and every
row fetched from a non-local shard moves through the emulated
vocab-parallel collective (`pm.embedding.shard_partial_sum`: one
materialized (n, D) partial per shard — the single-host stand-in for the
all-reduce's wire bytes).  The plain baseline moves EVERY token's row
through it; the managed path moves only the compact intent-planned miss
buffer and serves cache hits locally.

Both variants serve identical replayed request traces through the same
queue/scheduler stack, run back-to-back per repetition; the reported
speedup is the median of per-rep throughput ratios (paired to cancel
this container's bursty co-tenant noise).  Writes ``BENCH_serve.json``
at the repo root next to BENCH_quick/BENCH_scale.

CLI: ``python -m benchmarks.serve_bench [--quick] [--auto] [--pipeline]
[--check-baseline BENCH_serve.json]`` — ``--check-baseline`` re-measures
a CI-sized arm and fails on a >15% paired regression vs the committed
numbers (with ``--auto``: the auto-vs-tuned ratio arm instead of the
managed-vs-plain arms; with ``--pipeline``: the §15 pipelined-vs-
sequential arm, which must also stay >= 1.0x with unchanged requeue
semantics).

Observability (DESIGN.md §14): ``--trace PATH`` / ``--metrics-out PATH``
run one extra fully-traced managed arm after the measured sections (so
tracing never perturbs the headline numbers) and write the Chrome trace
and the JSONL metrics/attribution sink — the artifacts ``python -m
repro.obs.report`` renders and CI uploads.  ``--check-trace-overhead``
measures tracing's enabled cost: alternating traced-vs-untraced runs on
the frozen tuned config (controller nondeterminism excluded), pooling
every run's per-round latencies per arm and comparing pooled medians
against ``TRACE_OVERHEAD_TOL`` (2%) discounted by an inline A/A drift
measurement (see ``check_trace_overhead``).
"""

from __future__ import annotations

import json
import os
import sys
import time
from dataclasses import replace
from typing import Dict, List

import numpy as np

from repro.obs import JsonlSink, SpanTracer
from repro.pm.controller import AUTO
from repro.serve import (DriftingZipfStream, ReplayStream, ServeConfig,
                         ServingRuntime)

from .common import emit, paired_guard, paired_pooled_ratio

_OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                    "BENCH_serve.json")

# deployment-scale cost model: a 64-way vocab-sharded table (the intent
# engine's own node cap) — managed wins scale with the shard count
# because only the miss buffer pays the collective
N_SHARDS = 64
V, D = 65536, 512
B, K = 64, 64            # requests per micro-batch x keys per request —
#                          workload geometry (arrival rate = B), not a
#                          tuned runtime knob
REPS = 7
ROUNDS = 32
MEASURE_FROM = 4
# zero-tuning arm: the acceptance is that the controller REACHES the
# hand-tuned throughput within a single run, so its measured window is
# the post-convergence segment — a longer run with the adaptation
# transient (~3-4 controller decisions) excluded from the clock, same
# window for both arms (the tuned arm is steady throughout, so the
# deeper measure_from does not advantage either side)
ROUNDS_AUTO = 64
MEASURE_FROM_AUTO = 24
BACKLOG = 10             # warmup backlog rounds enqueued before round 0:
#                          pinned (not derived from the replan cadence,
#                          which is now controller-owned and moves) so
#                          every arm replays the identical trace alignment
STEADY_WINDOW = 5        # rounds of pre-rotation steady state
REGRESSION_TOL = 1.15    # --check-baseline: fail beyond a 15% slowdown
AUTO_MIN_RATIO = 0.9     # acceptance (d): auto >= 0.9x hand-tuned
TRACE_OVERHEAD_TOL = 1.02  # --check-trace-overhead: tracing at default
#                            sampling may cost at most 2% pooled-median
#                            round latency (DESIGN.md §14 overhead budget)
PIPELINE_MIN_SPEEDUP = 1.1  # acceptance: the intent-lead-time pipeline
#                             (tenure staging prefetch + N-deep admission,
#                             DESIGN.md §15) >= 1.1x sequential served-rps
#                             at zipf >= 1.0
PIPE_CAPACITY = 512      # pipeline arms run capacity-constrained: the
#                          recurring hot band overflows the cache, which
#                          is the regime tenure staging eliminates work in
PIPE_ROUNDS = 64         # longer runs than the headline arms: the paired
#                          estimator pools PER-TENURE samples, so each run
#                          must span enough replan tenures to fill the pool

# The PR-6 hand-set values, FROZEN as the zero-tuning section's reference
# arm only — the operating config below carries no tuned knobs.  Do not
# "retune" these: the point of the comparison is that the controller
# starting blind matches what an operator once found by hand.
HAND_TUNED: Dict[str, object] = {
    "cache_capacity": 8192, "replan_every": 8, "refresh_every": 0,
    "double_buffer": False,
}


def _auto_cfg() -> ServeConfig:
    """The operating config: every runtime knob controller-owned."""
    return ServeConfig(vocab=V, batch_requests=B, keys_per_request=K,
                       cache_capacity=AUTO, replan_every=AUTO,
                       refresh_every=AUTO, double_buffer=AUTO,
                       n_shards=N_SHARDS, summary=False)


def _tuned_cfg() -> ServeConfig:
    """The frozen hand-tuned reference arm (see HAND_TUNED)."""
    return replace(_auto_cfg(), **HAND_TUNED)


def _run_once(table, cfg: ServeConfig, replay: ReplayStream, warm,
              rounds: int = ROUNDS, measure_from: int = MEASURE_FROM):
    rt = ServingRuntime(table, cfg)
    rt._managed_fn = warm._managed_fn
    rt._plain_fn = warm._plain_fn
    return rt.run(replay, rounds, warmup_backlog=BACKLOG,
                  measure_from=measure_from)


def _paired_runs(table, cfg_a: ServeConfig, cfg_b: ServeConfig,
                 replay: ReplayStream, reps: int, warm):
    """Interleaved A/B reps on the same replayed trace.

    The container's 2 CPUs see bursty co-tenant noise that can slow a
    whole run 2x; running the pair back-to-back and taking the *median of
    per-rep throughput ratios* cancels that common-mode noise, which
    separate medians cannot."""
    pairs = []
    for _ in range(reps):
        a = _run_once(table, cfg_a, replay, warm)
        b = _run_once(table, cfg_b, replay, warm)
        pairs.append((a.throughput_rps / max(b.throughput_rps, 1e-9), a, b))
    pairs.sort(key=lambda t: t[0])
    return pairs[len(pairs) // 2]


def _tenure_means(table, cfg: ServeConfig, replay: ReplayStream, warm,
                  sink: List = None) -> List[float]:
    """One pipeline-arm run, reduced to per-tenure mean round latencies.

    Per-RUN wall clocks on this 2-CPU container have a ~20-30% co-tenant
    noise floor, and per-ROUND medians are biased FOR the pipeline (the
    median drops the few replan-boundary rounds where staging's extra
    costs land).  Per-TENURE means are both: every boundary's plan/stage/
    refresh cost is inside exactly one sample, and a ~100ms tenure is
    short enough that pooling `reps x tenures` samples per arm lets the
    median shrug off bursts that per-run aggregates cannot."""
    rt = ServingRuntime(table, cfg)
    rt._managed_fn = warm._managed_fn
    rt._plain_fn = warm._plain_fn
    res = rt.run(replay, PIPE_ROUNDS, warmup_backlog=BACKLOG,
                 measure_from=MEASURE_FROM)
    if sink is not None:
        sink.append(res)
    ms = rt.telemetry.latency("serve.round_ms").values()
    bounds = ([MEASURE_FROM]
              + [r for r in res.replan_rounds if r >= MEASURE_FROM]
              + [PIPE_ROUNDS])
    return [float(np.mean(ms[lo:hi]))
            for lo, hi in zip(bounds, bounds[1:]) if hi - lo >= 2]


def _pipeline_arm(table, replay: ReplayStream, reps: int, warm):
    """The §15 paired arm: depth-1 pipelined runtime (tenure staging
    prefetch + deferred blocking) vs the depth-0 sequential loop, same
    frozen knobs, same capacity-constrained cache, same replayed trace.
    Returns (stats, pipe_results, seq_results) where ``stats`` is the
    `paired_pooled_ratio` dict over per-tenure latency samples (base =
    sequential, test = pipelined — speedup is median_base/median_test)."""
    seq_cfg = replace(_tuned_cfg(), pipeline_depth=0,
                      cache_capacity=PIPE_CAPACITY)
    pipe_cfg = replace(_tuned_cfg(), pipeline_depth=1,
                       cache_capacity=PIPE_CAPACITY)
    # throwaway full-length runs: every tenure's staged/residual bucket
    # shape compiles outside the measured reps
    _tenure_means(table, pipe_cfg, replay, warm)
    _tenure_means(table, seq_cfg, replay, warm)
    pipe_res: List = []
    seq_res: List = []
    stats = paired_pooled_ratio(
        lambda: _tenure_means(table, seq_cfg, replay, warm, seq_res),
        lambda: _tenure_means(table, pipe_cfg, replay, warm, pipe_res),
        reps=reps)
    return stats, pipe_res, seq_res


def _warm(table, cfg: ServeConfig, replay: ReplayStream):
    rt = ServingRuntime(table, cfg)
    rt.run(replay, max(10, MEASURE_FROM + 4), warmup_backlog=BACKLOG,
           measure_from=2)
    return rt


def _record(zipf_a: float, rot: int, extra: int = 4) -> ReplayStream:
    scenario = "rotate" if rot else "steady"
    stream = DriftingZipfStream(V, K, zipf_a=zipf_a, arrival_rate=B,
                                scenario=scenario, rotate_every=rot or 32,
                                seed=3)
    return ReplayStream.record(stream, ROUNDS + BACKLOG + extra)


def _drift_metrics(res, rotation_rounds: List[int]) -> List[Dict]:
    """Per-rotation recovery analysis over the runtime's miss trace.

    A rotation at stream round R changes arrivals enqueued at runtime
    round R - backlog, which reach the scheduler ~backlog rounds later —
    so its effect on *served* traffic starts at runtime round ~R (the
    steady-state queue depth equals the warmup backlog).  Replans may
    adapt even earlier, from the rotated intent still queued."""
    trace = dict(res.miss_trace)
    out = []
    rots = list(rotation_rounds)
    for i, rot in enumerate(rots):
        if rot <= STEADY_WINDOW or rot >= res.rounds - 2:
            continue
        nxt = rots[i + 1] if i + 1 < len(rots) else res.rounds
        pre = res.steady_miss_rate(rot - STEADY_WINDOW, rot)
        replans = [r for r in res.replan_rounds if r >= rot]
        if pre is None or not replans:
            continue
        rr = replans[0]
        spike = max((trace[r] for r in range(rot, rr + 1) if r in trace),
                    default=pre)
        rec_hi = min(nxt, rr + 1 + STEADY_WINDOW)
        recovered = res.steady_miss_rate(rr + 1, rec_hi)
        if recovered is None:
            # no executed batch between the replan and the next rotation:
            # nothing measured, so nothing may be claimed — skip, and the
            # headline bool below requires at least one measured entry
            continue
        ratio = recovered / max(pre, 1e-9)
        out.append({
            "rotation_round": rot,
            "pre_rotation_miss": round(pre, 4),
            "spike_miss": round(spike, 4),
            "recovered_miss": round(recovered, 4),
            "recovery_ratio_vs_pre": round(ratio, 3),
            "replan_lag_rounds": rr - rot,
            "recovered_within_one_replan": bool(ratio <= 2.0),
        })
    return out


def _auto_pairs(table, replay: ReplayStream, reps: int, warm):
    """Paired auto-vs-tuned reps over the converged window.

    The two arms differ by only a few percent, so two bias sources the
    big managed-vs-plain margins shrug off matter here: run ORDER within
    a pair (allocator/cache spillover worth ~3-10%) is cancelled by
    alternating which arm runs first, and the adaptation transient is
    excluded by the MEASURE_FROM_AUTO window."""
    pairs = []
    for i in range(reps):
        if i % 2 == 0:
            a = _run_once(table, _auto_cfg(), replay, warm,
                          rounds=ROUNDS_AUTO,
                          measure_from=MEASURE_FROM_AUTO)
            t = _run_once(table, _tuned_cfg(), replay, warm,
                          rounds=ROUNDS_AUTO,
                          measure_from=MEASURE_FROM_AUTO)
        else:
            t = _run_once(table, _tuned_cfg(), replay, warm,
                          rounds=ROUNDS_AUTO,
                          measure_from=MEASURE_FROM_AUTO)
            a = _run_once(table, _auto_cfg(), replay, warm,
                          rounds=ROUNDS_AUTO,
                          measure_from=MEASURE_FROM_AUTO)
        pairs.append((a.throughput_rps / max(t.throughput_rps, 1e-9),
                      a, t))
    pairs.sort(key=lambda t: t[0])
    return pairs[len(pairs) // 2]


def _auto_section(table, skews: List[float], reps: int) -> Dict:
    """Zero-tuning acceptance arm: the controller starting from untuned
    defaults (ladder-floor capacity, short cadence) vs the frozen
    hand-tuned reference, paired on the same trace per skew."""
    entries = []
    warm = None
    for zipf_a in skews:
        replay = _record(zipf_a, 0, extra=ROUNDS_AUTO - ROUNDS + 4)
        if warm is None:
            # one shared compile cache across arms (same jit fns, shapes
            # re-specialize per capacity bucket); the throwaway tuned run
            # routes through warm's fns so the tuned shapes compile
            # outside the measured reps
            warm = _warm(table, _auto_cfg(), replay)
            _run_once(table, _tuned_cfg(), replay, warm)
        ratio, a, t = _auto_pairs(table, replay, reps, warm)
        entries.append({
            "zipf": zipf_a,
            "auto_rps": round(a.throughput_rps, 1),
            "tuned_rps": round(t.throughput_rps, 1),
            "auto_vs_tuned_x": round(ratio, 3),
            "meets_min_ratio": bool(ratio >= AUTO_MIN_RATIO),
            "final_knobs": a.knobs,
            "capacity_resizes": a.capacity_resizes,
            "capacity_trace": a.capacity_trace,
            "zero_served": a.zero_served,
            "replans": a.replans,
        })
    return {
        "untuned_start": {"cache_capacity": 64, "replan_every": 4,
                          "refresh_every": 0, "double_buffer": False},
        "hand_tuned_reference": HAND_TUNED,
        "min_ratio_required": AUTO_MIN_RATIO,
        "rounds": ROUNDS_AUTO,
        "measured_from_round": MEASURE_FROM_AUTO,
        "entries": entries,
        "all_meet_min_ratio": all(e["meets_min_ratio"] for e in entries),
        "zero_served_across_resizes": sum(
            e["zero_served"] for e in entries),
        "total_capacity_resizes": sum(
            e["capacity_resizes"] for e in entries),
    }


def _traced_arm(table, trace_path, metrics_path) -> None:
    """One fully-traced managed run on a drifting trace, AFTER the
    measured sections: writes the Chrome trace and the JSONL
    metrics/attribution sink (the report CLI's and CI's artifacts)."""
    replay = _record(1.1, 12)
    tracer = SpanTracer()
    rt = ServingRuntime(table, replace(_tuned_cfg(), trace=True),
                        tracer=tracer)
    res = rt.run(replay, ROUNDS, warmup_backlog=BACKLOG,
                 measure_from=MEASURE_FROM)
    assert len(rt.attribution.records) == res.replans, \
        "one attribution record per replan boundary"
    if trace_path:
        tracer.dump(trace_path)
        print(f"wrote {trace_path} ({tracer.count} spans, "
              f"{tracer.dropped} dropped)")
    if metrics_path:
        with JsonlSink(metrics_path) as sink:
            sink.write_bus(rt.telemetry, label="serve_bench traced arm")
            sink.write_attribution(rt.attribution.records)
        print(f"wrote {metrics_path}")


def check_trace_overhead(reps: int = 6) -> None:
    """CI guard for the §14 overhead budget: tracing enabled at default
    sampling must cost < 2% paired-median serve round latency.

    Estimator: `benchmarks.common.paired_guard` — both arms run the
    frozen tuned config (no controller nondeterminism) on the same
    replayed trace in alternating order, every run's per-round
    ``serve.round_ms`` samples pooled per arm, pooled-median ratio
    against ``TRACE_OVERHEAD_TOL`` discounted by the inline A/A drift
    split, best-of-two (the PR-8 methodology, since shared with the
    §15 pipeline guards)."""
    rng = np.random.default_rng(0)
    table = rng.normal(size=(V, D)).astype(np.float32)
    replay = _record(1.1, 0)
    warm = _warm(table, _tuned_cfg(), replay)

    def rounds_ms(traced: bool) -> List[float]:
        rt = ServingRuntime(table, replace(_tuned_cfg(), trace=traced))
        rt._managed_fn = warm._managed_fn
        rt._plain_fn = warm._plain_fn
        rt.run(replay, ROUNDS, warmup_backlog=BACKLOG,
               measure_from=MEASURE_FROM)
        return rt.telemetry.latency("serve.round_ms").values()

    paired_guard("trace overhead", lambda: rounds_ms(False),
                 lambda: rounds_ms(True), tol=TRACE_OVERHEAD_TOL,
                 reps=reps)


def run(quick: bool = False, trace_path: str = None,
        metrics_path: str = None) -> List[str]:
    t_start = time.time()
    rows: List[str] = []
    skews = [1.0, 1.1] if quick else [1.0, 1.1, 1.5]
    drift_rates = [0, 12] if quick else [0, 12, 20]   # rotate_every rounds
    reps = REPS if quick else REPS + 2
    # acceptance (d) is stated over all three skews — measure them even in
    # quick mode (the auto arm is cheap: one steady trace per skew)
    auto_skews = [1.0, 1.1, 1.5]

    rng = np.random.default_rng(0)
    table = rng.normal(size=(V, D)).astype(np.float32)
    base = _auto_cfg()

    throughput = []
    drift_entries = []
    zero_served_total = 0
    served_total = 0
    requeues_total = 0

    warm = None
    for zipf_a in skews:
        for rot in drift_rates:
            replay = _record(zipf_a, rot)
            tag = f"zipf{zipf_a}_rot{rot}"
            if warm is None:
                warm = _warm(table, base, replay)
                pwarm = _warm(table, replace(base, managed=False), replay)
                warm._plain_fn = pwarm._plain_fn

            speedup, m, p = _paired_runs(
                table, base, replace(base, managed=False), replay, reps,
                warm)
            zero_served_total += m.zero_served
            served_total += m.served + p.served
            requeues_total += m.requeues
            plain_rps, plain_p50, plain_p99 = (
                p.throughput_rps, p.p50_ms, p.p99_ms)
            emit(rows, "serve", "managed", tag, "throughput_rps",
                 round(m.throughput_rps, 1))
            emit(rows, "serve", "plain", tag, "throughput_rps",
                 round(plain_rps, 1))
            emit(rows, "serve", "managed", tag, "speedup_x",
                 round(speedup, 2))
            emit(rows, "serve", "managed", tag, "p50_ms",
                 round(m.p50_ms, 2))
            emit(rows, "serve", "managed", tag, "p99_ms",
                 round(m.p99_ms, 2))
            throughput.append({
                "zipf": zipf_a, "rotate_every": rot,
                "managed_rps": round(m.throughput_rps, 1),
                "plain_rps": round(plain_rps, 1),
                "speedup_x": round(speedup, 2),
                "managed_p50_ms": round(m.p50_ms, 2),
                "managed_p99_ms": round(m.p99_ms, 2),
                "plain_p50_ms": round(plain_p50, 2),
                "plain_p99_ms": round(plain_p99, 2),
                "steady_miss_rate": round(
                    m.steady_miss_rate(MEASURE_FROM, m.rounds) or 0.0, 4),
                "requeues": m.requeues, "zero_served": m.zero_served,
                "final_knobs": m.knobs,
            })
            if rot:
                for entry in _drift_metrics(m, replay.rotation_rounds):
                    entry.update({"zipf": zipf_a, "rotate_every": rot})
                    drift_entries.append(entry)
                    emit(rows, "serve", "managed", tag,
                         "recovery_ratio_vs_pre",
                         entry["recovery_ratio_vs_pre"])

    # double-buffered admission (the probe-at-admission split means batch
    # t+1's whole index stage can run while the device executes batch t):
    # paired managed-vs-managed comparison, pipeline on vs off, same
    # trace, other knobs pinned to the frozen reference so the pipeline
    # is the only variable
    ov_replay = _record(1.1, 0)
    serial = _tuned_cfg()
    buffered = replace(serial, double_buffer=True)
    ov_win, ov_d, ov_s = _paired_runs(table, buffered, serial, ov_replay,
                                      reps, warm)
    emit(rows, "serve", "managed", "zipf1.1_steady", "overlap_win_x",
         round(ov_win, 3))
    overlap = {
        "double_buffer_rps": round(ov_d.throughput_rps, 1),
        "serial_rps": round(ov_s.throughput_rps, 1),
        "overlap_win_x": round(ov_win, 3),
        "double_buffer_p50_ms": round(ov_d.p50_ms, 2),
        "serial_p50_ms": round(ov_s.p50_ms, 2),
        # the telemetry record the runtime's own auto-enable rule reads
        "measured_overlap_ratio": round(warm.overlap_ratio, 3)
        if warm.overlap_ratio is not None else None,
    }

    # intent-lead-time pipeline (DESIGN.md §15): tenure staging prefetch
    # + N-deep admission vs the depth-0 sequential loop, same knobs and
    # the same drifting zipf-1.0 trace, so the pipeline is the only
    # variable.  Both arms run CAPACITY-CONSTRAINED (cache far below the
    # recurring working set): that is the regime staging prefetch is
    # for — the hot band the plan cannot cache recurs in every batch's
    # miss bucket, and the staging buffer gathers it from the table once
    # per tenure instead of once per round.  The win on this single-core
    # host is that WORK ELIMINATION, not overlap.  (At the reference
    # capacity the planner caches all recurring intent and the staging
    # buffer degenerates to the count-1 tail — nothing to eliminate.)
    pl_replay = _record(1.0, 12, extra=PIPE_ROUNDS - ROUNDS + 4)
    pl, pl_pres, pl_sres = _pipeline_arm(table, pl_replay, reps, warm)
    pl_win = pl["median_base"] / pl["median_test"]
    # one extra instrumented run for the prefetch hit/stale counters
    irt = ServingRuntime(table, replace(_tuned_cfg(), pipeline_depth=1,
                                        cache_capacity=PIPE_CAPACITY))
    irt._managed_fn = warm._managed_fn
    irt._plain_fn = warm._plain_fn
    irt.run(pl_replay, PIPE_ROUNDS, warmup_backlog=BACKLOG,
            measure_from=MEASURE_FROM)
    ph = int(irt.telemetry.counter_value("serve.prefetch_hits"))
    ps = int(irt.telemetry.counter_value("serve.prefetch_stale"))
    emit(rows, "serve", "pipelined", "zipf1.0_rot12", "serve_win_x",
         round(pl_win, 3))
    pipeline = {
        "zipf": 1.0, "rotate_every": 12, "pipeline_depth": 1,
        "cache_capacity": PIPE_CAPACITY, "rounds": PIPE_ROUNDS,
        # served-req/s from the pooled per-tenure medians (B requests
        # served per round in both arms — verified by the semantics check
        # below — so rps is B over the pooled mean-round latency)
        "pipelined_rps": round(B * 1e3 / pl["median_test"], 1),
        "sequential_rps": round(B * 1e3 / pl["median_base"], 1),
        "serve_win_x": round(pl_win, 3),
        "min_speedup_required": PIPELINE_MIN_SPEEDUP,
        "meets_min_speedup": bool(pl_win >= PIPELINE_MIN_SPEEDUP),
        "sequential_tenure_ms": round(pl["median_base"], 3),
        "pipelined_tenure_ms": round(pl["median_test"], 3),
        "aa_drift": round(pl["drift"], 4),
        "samples_per_arm": pl["samples_per_arm"],
        "zero_served": sum(r.zero_served for r in pl_pres + pl_sres),
        "requeues_pipelined": sum(r.requeues for r in pl_pres),
        "requeues_sequential": sum(r.requeues for r in pl_sres),
        # same trace, same probe decisions: the pipeline may not change
        # WHAT is served or requeued, only when the host blocks
        "requeue_semantics_unchanged": bool(
            sum(r.requeues for r in pl_pres)
            == sum(r.requeues for r in pl_sres)
            and sum(r.served for r in pl_pres)
            == sum(r.served for r in pl_sres)),
        "prefetch_hits": ph, "prefetch_stale": ps,
        "staged_cover_rate": round(ph / max(ph + ps, 1), 4),
    }

    auto = _auto_section(table, auto_skews, reps)
    for e in auto["entries"]:
        emit(rows, "serve", "auto", f"zipf{e['zipf']}", "auto_vs_tuned_x",
             e["auto_vs_tuned_x"])

    speedups = [t["speedup_x"] for t in throughput]
    summary = {
        "config": {"vocab": V, "dim": D, "batch_requests": B,
                   "keys_per_request": K,
                   "cache_capacity": AUTO, "replan_every": AUTO,
                   "refresh_every": AUTO, "double_buffer": AUTO,
                   "n_shards": N_SHARDS,
                   "reps": reps, "rounds": ROUNDS, "quick": quick},
        "throughput": throughput,
        "overlap": overlap,
        "pipeline": pipeline,
        "auto": auto,
        "min_speedup_at_zipf_ge_1.0": min(speedups),
        "drift": drift_entries,
        # non-vacuous: requires at least one measured post-replan window
        "drift_all_recovered_within_one_replan": bool(drift_entries) and
        all(e["recovered_within_one_replan"] for e in drift_entries),
        "zero_served_total": zero_served_total,
        "requeues_total": requeues_total,
        "requests_served_total": served_total,
        "wall_clock_s": round(time.time() - t_start, 2),
    }
    with open(_OUT, "w") as f:
        json.dump(summary, f, indent=1)
    print(f"wrote {os.path.normpath(_OUT)}")
    if trace_path or metrics_path:
        _traced_arm(table, trace_path, metrics_path)
    emit(rows, "serve", "managed", "ALL", "min_speedup_x",
         round(min(speedups), 2))
    emit(rows, "serve", "managed", "ALL", "zero_served", zero_served_total)
    emit(rows, "serve", "auto", "ALL", "min_auto_vs_tuned_x",
         round(min(e["auto_vs_tuned_x"] for e in auto["entries"]), 3))
    return rows


def check_baseline(path: str, auto: bool = False,
                   pipeline: bool = False) -> None:
    """CI guard: re-measure a small arm and compare against the committed
    BENCH_serve.json.  Paired ratios normalize away absolute host speed;
    the guard trips only when today's ratio falls >15% below the
    committed one (geomean across arms, best-of-two on a first trip to
    ride out co-tenant bursts).

    Default arm: managed-vs-plain speedups at zipf {1.0, 1.1}, steady.
    ``auto=True``: the zero-tuning arm — auto-vs-tuned ratio at zipf 1.1,
    which additionally must clear the absolute AUTO_MIN_RATIO floor.
    ``pipeline=True``: the §15 arm — pipelined-vs-sequential served-rps
    on the drifting zipf-1.0 trace, which additionally requires zero
    zero-served batches and unchanged requeue counts (the pipeline is a
    wall-clock transform, never a semantics change)."""
    with open(path) as f:
        committed = json.load(f)
    rng = np.random.default_rng(0)
    table = rng.normal(size=(V, D)).astype(np.float32)
    reps = 3

    def measure() -> Dict[str, float]:
        if pipeline:
            replay = _record(1.0, 12, extra=PIPE_ROUNDS - ROUNDS + 4)
            warm = _warm(
                table, replace(_tuned_cfg(), pipeline_depth=1,
                               cache_capacity=PIPE_CAPACITY), replay)
            stats, pres, sres = _pipeline_arm(table, replay, reps, warm)
            if any(r.zero_served for r in pres + sres):
                raise SystemExit("pipeline arm served zeroed batches")
            prq, srq = (sum(r.requeues for r in pres),
                        sum(r.requeues for r in sres))
            psv, ssv = (sum(r.served for r in pres),
                        sum(r.served for r in sres))
            if prq != srq or psv != ssv:
                raise SystemExit(
                    f"pipeline arm changed serve semantics: requeues "
                    f"{prq} vs {srq}, served {psv} vs {ssv}")
            return {"pipeline_zipf1.0":
                    stats["median_base"] / stats["median_test"]}
        if auto:
            replay = _record(1.1, 0, extra=ROUNDS_AUTO - ROUNDS + 4)
            warm = _warm(table, _auto_cfg(), replay)
            _run_once(table, _tuned_cfg(), replay, warm)
            ratio, a, _ = _auto_pairs(table, replay, reps, warm)
            if a.zero_served:
                raise SystemExit(f"auto arm served {a.zero_served} "
                                 "zeroed rows across capacity resizes")
            return {"auto_zipf1.1": ratio}
        out = {}
        warm = None
        for zipf_a in (1.0, 1.1):
            replay = _record(zipf_a, 0)
            if warm is None:
                warm = _warm(table, _auto_cfg(), replay)
                warm._plain_fn = _warm(
                    table, replace(_auto_cfg(), managed=False),
                    replay)._plain_fn
            ratio, _, _ = _paired_runs(
                table, _auto_cfg(), replace(_auto_cfg(), managed=False),
                replay, reps, warm)
            out[f"managed_zipf{zipf_a}"] = ratio
        return out

    def reference() -> Dict[str, float]:
        if pipeline:
            sec = committed.get("pipeline")
            if not sec:
                raise SystemExit("committed baseline has no pipeline "
                                 "section — regenerate BENCH_serve.json")
            return {"pipeline_zipf1.0": sec["serve_win_x"]}
        if auto:
            entries = committed.get("auto", {}).get("entries", [])
            ref = {f"auto_zipf{e['zipf']}": e["auto_vs_tuned_x"]
                   for e in entries if e["zipf"] == 1.1}
            if not ref:
                raise SystemExit("committed baseline has no auto section "
                                 "at zipf 1.1 — regenerate BENCH_serve"
                                 ".json")
            return ref
        ref = {}
        for t in committed["throughput"]:
            if t["rotate_every"] == 0 and t["zipf"] in (1.0, 1.1):
                ref[f"managed_zipf{t['zipf']}"] = t["speedup_x"]
        if not ref:
            raise SystemExit("committed baseline has no steady arms")
        return ref

    ref = reference()

    def verdict(meas: Dict[str, float]):
        rel = [meas[k] / ref[k] for k in ref if k in meas]
        geo = float(np.exp(np.mean(np.log(np.maximum(rel, 1e-9)))))
        floor_ok = True
        if auto:
            floor_ok = all(meas[k] >= AUTO_MIN_RATIO for k in meas)
        if pipeline:
            floor_ok = all(meas[k] >= 1.0 for k in meas)
        return geo, geo * REGRESSION_TOL >= 1.0 and floor_ok

    meas = measure()
    geo, ok = verdict(meas)
    if not ok:
        # one retry: a co-tenant burst can eat a whole measurement pass
        meas2 = measure()
        meas = {k: max(meas[k], meas2[k]) for k in meas}
        geo, ok = verdict(meas)
    arm = ("pipelined-vs-sequential" if pipeline
           else "auto-vs-tuned" if auto else "managed-vs-plain")
    detail = " ".join(f"{k}={meas[k]:.2f}(ref {ref[k]:.2f})"
                      for k in sorted(ref) if k in meas)
    if not ok:
        raise SystemExit(
            f"serve {arm} regression: geomean {geo:.3f}x of committed "
            f"(tolerance {1 / REGRESSION_TOL:.3f}) — {detail}")
    print(f"serve {arm} baseline ok: geomean {geo:.3f}x of committed "
          f"— {detail}")


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized smoke (2 skews x 2 drift rates)")
    ap.add_argument("--auto", action="store_true",
                    help="with --check-baseline: guard the zero-tuning "
                         "arm instead of managed-vs-plain")
    ap.add_argument("--pipeline", action="store_true",
                    help="with --check-baseline: guard the §15 "
                         "pipelined-vs-sequential arm")
    ap.add_argument("--check-baseline", metavar="JSON", default=None,
                    help="re-measure a small arm and fail on a >15%% "
                         "paired regression vs the committed numbers")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="write a fully-traced arm's Chrome trace JSON")
    ap.add_argument("--metrics-out", metavar="PATH", default=None,
                    help="write the traced arm's telemetry + attribution "
                         "records as schema-versioned JSONL")
    ap.add_argument("--check-trace-overhead", action="store_true",
                    help="fail if tracing at default sampling costs >2%% "
                         "paired-median throughput")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.check_baseline:
        check_baseline(args.check_baseline, auto=args.auto,
                       pipeline=args.pipeline)
        sys.exit(0)
    if args.check_trace_overhead:
        check_trace_overhead()
        sys.exit(0)
    run(quick=args.quick, trace_path=args.trace,
        metrics_path=args.metrics_out)
