"""Tests for the zero-tuning control plane (DESIGN.md §13): the telemetry
bus, the online controller (signal rules + hill-climb + settling), and the
mid-run replica-cache capacity resize — byte-identical serving results
across every resize boundary, with the per-bucket jit cache never
recompiling a revisited bucket."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import blocking
from repro.obs import Reservoir, Telemetry, default_bus
from repro.pm.controller import (AUTO, Knob, OnlineController,
                                 capacity_ladder, is_auto, overlap_pays,
                                 pow2_ladder, resolve_knob)
from repro.serve import (DriftingZipfStream, ReplayStream, ServeConfig,
                         ServeRequest, ServingRuntime)


class TestTelemetry:
    def test_counter_gauge_reservoir_roundtrip(self):
        bus = Telemetry()
        bus.inc("serve.replans")
        bus.inc("serve.replans", 2)
        assert bus.counter_value("serve.replans") == 3
        assert bus.counter_value("never.touched") == 0
        bus.set("serve.miss_rate", 0.25)
        bus.set("serve.miss_rate", 0.5)          # last write wins
        assert bus.gauge_value("serve.miss_rate") == 0.5
        assert bus.gauge_value("never.touched", default=7.0) == 7.0
        bus.observe("serve.round_ms", 1.0)
        bus.observe("serve.round_ms", 3.0)
        r = bus.latency("serve.round_ms")
        assert r.count == 2
        assert r.percentile(50) == 2.0

    def test_labels_are_distinct_keys_not_aggregated(self):
        bus = Telemetry()
        bus.inc("serve.replans", cause="drift")
        bus.inc("serve.replans", cause="cadence")
        bus.inc("serve.replans", cause="cadence")
        assert bus.counter_value("serve.replans", cause="drift") == 1
        assert bus.counter_value("serve.replans", cause="cadence") == 2
        # the label-free parent is NOT implicitly summed
        assert bus.counter_value("serve.replans") == 0

    def test_events_ordered_and_filterable(self):
        bus = Telemetry()
        bus.event("ctl.force", knob="cache_capacity", value=512)
        bus.event("serve.replan", round=3)
        bus.event("ctl.force", knob="cache_capacity", value=1024)
        forces = bus.events("ctl.force")
        assert [e["value"] for e in forces] == [512, 1024]
        assert forces[0]["_seq"] < forces[1]["_seq"]
        assert len(bus.events()) == 3

    def test_reservoir_bounds_memory_not_count(self):
        r = Reservoir(maxlen=8)
        for v in range(100):
            r.record(float(v))
        assert r.count == 100
        assert len(r._vals) == 8
        assert 0.0 <= r.percentile(50) <= 99.0

    def test_snapshot_and_summary_line(self):
        bus = Telemetry()
        bus.inc("a.count", 4)
        bus.set("b.gauge", 1.5)
        bus.observe("c.lat_ms", 2.0)
        snap = bus.snapshot()
        assert snap["counters"]["a.count"] == 4
        assert snap["gauges"]["b.gauge"] == 1.5
        assert snap["latencies"]["c.lat_ms"]["count"] == 1
        line = bus.summary_line(prefix="test")
        assert line.startswith("[test] ")
        assert "a.count=4" in line and "b.gauge=1.5" in line
        assert "c.lat_ms[p50=" in line


class TestKnobHelpers:
    def test_auto_sentinel_and_resolution(self):
        assert is_auto(AUTO) and is_auto("auto")
        assert not is_auto(64) and not is_auto(True)
        assert resolve_knob(AUTO, 64) == 64
        assert resolve_knob(512, 64) == 512

    def test_ladders_are_pow2_buckets(self):
        assert pow2_ladder(8, 256) == (8, 16, 32, 64, 128, 256)
        lad = capacity_ladder(65536)
        assert lad[0] == 64 and lad[-1] == 8192
        assert all(b == 2 * a for a, b in zip(lad, lad[1:]))
        # tiny vocab: ladder never collapses below the floor bucket
        assert capacity_ladder(128) == (64,)

    def test_overlap_pays_rule(self):
        assert not overlap_pays(None)
        assert not overlap_pays(1.1)
        assert overlap_pays(1.2)
        assert overlap_pays(1.05, threshold=1.0)


def _ctl(knobs, **kw):
    bus = Telemetry()
    kw.setdefault("epsilon", 0.0)        # deterministic cycle for units
    return OnlineController(knobs, bus, **kw), bus


class TestControllerSignalRules:
    def test_force_at_least_jumps_to_covering_bucket(self):
        ctl, bus = _ctl([Knob("cache_capacity", (64, 128, 256, 512),
                              adapt=False)])
        assert ctl.force_at_least("cache_capacity", 200) == 256
        assert ctl.value("cache_capacity") == 256
        # already covered: no move, no event
        assert ctl.force_at_least("cache_capacity", 100) is None
        # beyond the top: clamps to the last bucket
        assert ctl.force_at_least("cache_capacity", 10_000) == 512
        assert [e["value"] for e in bus.events("ctl.force")] == [256, 512]

    def test_steer_capacity_grows_now_shrinks_patiently(self):
        ctl, bus = _ctl([Knob("cache_capacity", (64, 256, 1024, 4096),
                              adapt=False, prefer_low=True)],
                        shrink_patience=2)
        # hard signal: demand jumps straight to the covering bucket
        assert ctl.steer_capacity("cache_capacity", 900) == 1024
        # low demand with >= 4x gap: first sighting only starts the streak
        assert ctl.steer_capacity("cache_capacity", 40) is None
        assert ctl.value("cache_capacity") == 1024
        # second consecutive low replan: the shrink lands
        assert ctl.steer_capacity("cache_capacity", 40) == 64
        causes = [e["cause"] for e in bus.events("ctl.force")]
        assert causes == ["demand", "demand_low"]

    def test_demand_spike_resets_the_shrink_streak(self):
        ctl, _ = _ctl([Knob("cache_capacity", (64, 256, 1024),
                            adapt=False)], shrink_patience=2)
        ctl.steer_capacity("cache_capacity", 1000)
        ctl.steer_capacity("cache_capacity", 10)       # streak = 1
        ctl.steer_capacity("cache_capacity", 900)      # spike: streak reset
        assert ctl.steer_capacity("cache_capacity", 10) is None
        assert ctl.value("cache_capacity") == 1024

    def test_mild_demand_drop_never_shrinks(self):
        # hysteresis: shrink needs a >= 4x gap, not just "lower"
        ctl, _ = _ctl([Knob("cache_capacity", (64, 256, 1024),
                            adapt=False)], shrink_patience=1)
        ctl.steer_capacity("cache_capacity", 1000)
        for _ in range(5):
            assert ctl.steer_capacity("cache_capacity", 400) is None
        assert ctl.value("cache_capacity") == 1024


class TestControllerHillClimb:
    def test_accept_keeps_move_revert_restores(self):
        ctl, bus = _ctl([Knob("replan_every", (2, 4, 8, 16), index=1)])
        assert ctl.observe(100.0) == {"replan_every": 8}   # propose up
        assert ctl.observe(120.0) == {}                    # improved: keep
        assert ctl.value("replan_every") == 8
        assert ctl.observe(120.0) == {"replan_every": 16}  # next trial
        assert ctl.observe(90.0) == {"replan_every": 8}    # worse: revert
        trials = bus.events("ctl.trial")
        assert [t["accepted"] for t in trials] == [True, False]

    def test_prefer_low_accepts_a_tie_downward(self):
        k = Knob("cache_capacity", (64, 128, 256), index=2, prefer_low=True)
        ctl, _ = _ctl([k], tol=0.05)
        ctl._last_dir["cache_capacity"] = -1
        assert ctl.observe(100.0) == {"cache_capacity": 128}
        # same throughput for less resource: the downward move sticks
        assert ctl.observe(98.0) == {}
        assert ctl.value("cache_capacity") == 128

    def test_ladder_edges_bounce_direction(self):
        ctl, _ = _ctl([Knob("b", (8, 16), index=1)])
        ctl._last_dir["b"] = 1
        assert ctl.observe(1.0) == {"b": 8}    # up blocked: bounces down
        ctl.observe(2.0)

    def test_settles_after_consecutive_reverts_and_unsettles_on_signal(self):
        ctl, bus = _ctl([Knob("replan_every", (2, 4, 8), index=1),
                         Knob("cache_capacity", (64, 256), adapt=False)],
                        settle_after=2)
        for _ in range(2):                     # two trials, both worse
            assert ctl.observe(100.0) != {}
            assert ctl.observe(50.0) == {"replan_every": 4}
        assert len(bus.events("ctl.settle")) == 1
        # settled: no further proposals tax steady-state throughput
        for _ in range(4):
            assert ctl.observe(100.0) == {}
        # a signal-rule move changes the regime: exploration reopens
        ctl.force_at_least("cache_capacity", 256)
        assert ctl.observe(100.0) == {"replan_every": 8}

    def test_adapt_false_knobs_never_hill_climbed(self):
        ctl, _ = _ctl([Knob("cache_capacity", (64, 256, 1024),
                            adapt=False)])
        for r in (1.0, 2.0, 3.0, 4.0):
            assert ctl.observe(r) == {}
        assert ctl.value("cache_capacity") == 64

    def test_same_seed_same_trajectory(self):
        def run(seed):
            ctl = OnlineController(
                [Knob("a", (2, 4, 8), index=1), Knob("b", (8, 16, 32))],
                Telemetry(), epsilon=0.3, seed=seed)
            rewards = [10, 12, 11, 13, 9, 14, 14, 8, 15, 15]
            return [dict(ctl.observe(float(r))) for r in rewards], \
                ctl.values()
        assert run(3) == run(3)


# --------------------------------------------------------------------------
# Mid-run capacity resize: exactness, zero-served, and jit-bucket reuse
# --------------------------------------------------------------------------

V, D, K, B = 2048, 16, 8, 16
BUCKETS = (64, 256, 1024)


def _record_trace(rounds, seed, rid_offset=0):
    stream = DriftingZipfStream(V, K, zipf_a=1.2, arrival_rate=B,
                                scenario="steady", seed=seed)
    per_round = [[ServeRequest(r.rid + rid_offset, r.keys)
                  for r in stream.arrivals(rnd)] for rnd in range(rounds)]
    by_rid = {r.rid: r.keys for row in per_round for r in row}
    return per_round, by_rid


def _drain_rounds(n_arrival_rounds, rt):
    # arrivals stop after the trace; extra empty rounds let the scheduler
    # drain the warm-up backlog so every segment ends with an empty queue
    return n_arrival_rounds + rt.replan_every + 6


class TestMidRunCapacityResize:
    @pytest.mark.parametrize("kernel", [False, True],
                             ids=["nokernel", "kernel"])
    def test_resize_across_buckets_byte_identical(self, kernel):
        """Segments served at capacities {64, 256, 1024} (and back down),
        resized mid-run via the public hook: every served row stays a
        byte-identical copy of the table row, no batch is ever
        zero-served, and revisiting a capacity bucket re-uses the jitted
        executables compiled on the first visit."""
        rng = np.random.default_rng(0)
        table = rng.normal(size=(V, D)).astype(np.float32)
        cfg = ServeConfig(vocab=V, batch_requests=B, keys_per_request=K,
                          cache_capacity=BUCKETS[0], replan_every=4,
                          refresh_every=0, double_buffer=False,
                          kernel=kernel, summary=False)
        rt = ServingRuntime(table, cfg)

        # pass 1 visits each bucket on a fresh trace; pass 2 revisits the
        # SAME key traces (fresh rids) at the same capacities, so every
        # (capacity, miss-capacity) shape repeats and the jit caches must
        # already hold it
        arrival_rounds = 10
        plan_pass = [(cap, i) for i, cap in enumerate(BUCKETS)]
        segments = plan_pass + [(cap, i + len(BUCKETS))
                                for i, cap in enumerate(BUCKETS)]
        traces, refs = [], {}
        for si, (cap, seed) in enumerate(segments):
            per_round, by_rid = _record_trace(
                arrival_rounds, seed=segments[si % len(plan_pass)][1],
                rid_offset=si * 100_000)
            traces.append(ReplayStream(per_round))
            refs.update(by_rid)

        sizes_after_first_pass = None
        for si, ((cap, _), replay) in enumerate(zip(segments, traces)):
            if rt.cache_capacity != cap:
                rt.resize_capacity(cap)
            res = rt.run(replay, rounds=_drain_rounds(arrival_rounds, rt),
                         collect_outputs=True)
            assert rt.cache_capacity == cap
            # exactness across the resize boundary: managed serving is a
            # pure gather no matter which rows the replica cache holds
            assert res.zero_served == 0, f"segment {si} (cap={cap})"
            assert res.outputs, f"segment {si} served nothing"
            for rid, rows in res.outputs.items():
                np.testing.assert_array_equal(
                    np.asarray(rows), table[refs[rid]],
                    err_msg=f"segment {si} cap={cap} rid={rid}")
            assert len(rt.queue) == 0    # drained: segments independent
            if si == len(plan_pass) - 1:
                sizes_after_first_pass = rt._managed_fn._cache_size()
        # repeat pass saw only already-compiled buckets
        assert rt._managed_fn._cache_size() == sizes_after_first_pass
        assert rt.telemetry.counter_value("serve.capacity_resizes") \
            == len(segments) - 1

    def test_controller_steered_resize_stays_exact(self):
        """cache_capacity="auto": the intent signal grows the bucket from
        the untuned floor mid-run, and the resize never costs a
        zero-served batch or an inexact row."""
        rng = np.random.default_rng(1)
        table = rng.normal(size=(V, D)).astype(np.float32)
        cfg = ServeConfig(vocab=V, batch_requests=B, keys_per_request=K,
                          cache_capacity=AUTO, replan_every=4,
                          refresh_every=0, double_buffer=False,
                          summary=False)
        rt = ServingRuntime(table, cfg)
        assert rt.cache_capacity == capacity_ladder(V)[0]  # untuned floor
        per_round, by_rid = _record_trace(24, seed=9)
        res = rt.run(ReplayStream(per_round), rounds=30,
                     collect_outputs=True)
        assert res.capacity_resizes >= 1
        assert res.capacity_trace[0][1] > capacity_ladder(V)[0]
        assert res.zero_served == 0
        for rid, rows in res.outputs.items():
            np.testing.assert_array_equal(np.asarray(rows), table[by_rid[rid]])
        # the steer is on the bus with its cause
        assert any(e["cause"] == "demand"
                   for e in rt.telemetry.events("ctl.force"))


class TestOverlapCalibrationTelemetry:
    def test_calibration_is_a_bus_record_not_a_startup_print(self, capsys):
        rng = np.random.default_rng(2)
        table = rng.normal(size=(V, D)).astype(np.float32)
        cfg = ServeConfig(vocab=V, batch_requests=B, keys_per_request=K,
                          cache_capacity=256, replan_every=4,
                          summary=False)
        rt = ServingRuntime(table, cfg)
        per_round, _ = _record_trace(6, seed=3)
        rt.run(ReplayStream(per_round), rounds=8)
        assert capsys.readouterr().out == ""     # silent run
        # ... but the measurement landed on the bus
        assert rt.overlap_ratio is not None
        assert rt.telemetry.gauge_value("serve.overlap_ratio") \
            == pytest.approx(rt.overlap_ratio)
        assert rt.telemetry.gauge_value("serve.overlap_host_ms") > 0

    def test_summary_prints_one_shutdown_line(self, capsys):
        rng = np.random.default_rng(2)
        table = rng.normal(size=(V, D)).astype(np.float32)
        cfg = ServeConfig(vocab=V, batch_requests=B, keys_per_request=K,
                          cache_capacity=256, replan_every=4,
                          summary=True)
        rt = ServingRuntime(table, cfg)
        per_round, _ = _record_trace(6, seed=3)
        rt.run(ReplayStream(per_round), rounds=8)
        rt.run(ReplayStream([]), rounds=2)       # second run: no re-print
        out = capsys.readouterr().out.strip().splitlines()
        assert len(out) == 1
        assert out[0].startswith("[serve] ") and "overlap~" in out[0]


class TestAutotuneTelemetry:
    def test_fresh_tile_decision_lands_on_default_bus_once(self):
        blocking.clear_autotune_cache()
        bus = default_bus()
        before = len(bus.events("autotune.blocks"))
        br, bd = blocking.pick_blocks("testkind", 96, 384)
        after_first = bus.events("autotune.blocks")[before:]
        assert len(after_first) == 1
        ev = after_first[0]
        assert ev["source"] in ("measured", "heuristic")
        assert (ev["block_r"], ev["block_d"]) == (br, bd)
        # cache re-hit: no duplicate event
        blocking.pick_blocks("testkind", 96, 384)
        assert len(bus.events("autotune.blocks")) == before + 1
