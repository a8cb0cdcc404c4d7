"""Tests for the observability layer (DESIGN.md §14): ring-buffered span
tracer, plan-vs-actual attribution, Prometheus/JSONL export, the report
CLI, per-tenant serve accounting, and shared-bus threading."""

import json

import numpy as np
import pytest

from repro.obs import (ATTRIBUTION_SCHEMA, SCHEMA_VERSION, JsonlSink,
                       PlanAttribution, Reservoir, SpanTracer, Telemetry,
                       make_tracer, prometheus_text, read_jsonl)
from repro.obs.report import main as report_main
from repro.obs.report import render_report, validate_chrome
from repro.serve import (DriftingZipfStream, RequestQueue, ServeConfig,
                         ServeRequest, ServingRuntime)
from repro.serve.scheduler import MicroBatchScheduler


class TestSpanTracer:
    def test_span_nesting_and_ordering(self):
        tr = SpanTracer()
        with tr.span("outer", a=1):
            with tr.span("inner", a=2):
                pass
        evs = tr.events()
        # inner closes (and records) first; both held oldest-first
        assert [e["name"] for e in evs] == ["inner", "outer"]
        inner, outer = evs
        assert outer["t0_ns"] <= inner["t0_ns"]
        assert inner["t1_ns"] <= outer["t1_ns"]
        assert (inner["a"], outer["a"]) == (2, 1)

    def test_ring_eviction_under_overflow(self):
        tr = SpanTracer(capacity=8)
        for i in range(20):
            t = tr.now_ns()
            tr.record("s", t, t + 1, a=i)
        assert tr.count == 20
        assert tr.dropped == 12
        evs = tr.events()
        assert len(evs) == 8
        # oldest held span first: 12..19 survive, 0..11 were evicted
        assert [e["a"] for e in evs] == list(range(12, 20))

    def test_disabled_tracer_emits_nothing(self):
        tr = SpanTracer(enabled=False)
        # one shared no-op context manager: no per-call allocation
        assert tr.span("x") is tr.span("y")
        with tr.span("x", a=1):
            pass
        tr.record("y", 0, 5)
        tr.point("z")
        assert tr.count == 0
        assert tr.events() == []
        assert tr.to_chrome()["traceEvents"] == []

    def test_sampling_is_deterministic_per_id(self):
        tr = SpanTracer(sample=0.5)
        first = [tr.sampled(i) for i in range(1000)]
        assert first == [tr.sampled(i) for i in range(1000)]
        frac = sum(first) / 1000.0
        assert 0.3 < frac < 0.7
        assert all(SpanTracer(sample=1.0).sampled(i) for i in range(50))
        assert not any(SpanTracer(sample=0.0).sampled(i) for i in range(50))

    def test_chrome_export_is_valid_trace_event_json(self):
        tr = SpanTracer()
        with tr.span("serve.dispatch", tid=3, a=7, b=9):
            pass
        tr.point("serve.requeue", a=4)
        doc = tr.to_chrome()
        events = validate_chrome(doc)          # raises on missing fields
        json.dumps(doc)
        by_name = {e["name"]: e for e in events}
        x = by_name["serve.dispatch"]
        assert x["ph"] == "X" and x["dur"] > 0 and x["tid"] == 3
        assert x["args"] == {"a": 7, "b": 9}
        inst = by_name["serve.requeue"]
        assert inst["ph"] == "i" and inst["s"] == "t"
        assert [e["ts"] for e in events] == sorted(e["ts"] for e in events)
        assert doc["otherData"]["spans_recorded"] == 2

    def test_make_tracer_injected_instance_wins(self):
        mine = SpanTracer(sample=0.25)
        assert make_tracer(False, tracer=mine) is mine
        assert not make_tracer(False).enabled
        assert make_tracer(True, sample=0.5).sample == 0.5


class TestAttribution:
    def test_hand_computed_record(self):
        """V=4 over 2 owner shards (block=2): tokens [0,1,2,2,3] with
        hits [T,T,F,F,F] miss 3/5 accesses, all on owner shard 1."""
        bus = Telemetry()
        at = PlanAttribution(owner_shards=2, vocab=4, telemetry=bus)
        at.note_batch(np.array([0, 1, 2, 2, 3]),
                      np.array([True, True, False, False, False]))
        rec = at.flush(rnd=5, plan=None, cause="drift",
                       knobs={"cache_capacity": 64}, capacity=64,
                       miss_capacity=16)
        assert rec.plan_version == 0           # no plan yet
        assert rec.predicted_miss_rate == 0.0
        assert rec.realized_miss_rate == pytest.approx(3 / 5)
        assert rec.miss_rate_error == pytest.approx(3 / 5)
        assert rec.per_owner_misses == {1: 3}
        assert rec.top_keys == [(2, 2), (3, 1)]
        assert (rec.batches, rec.tokens, rec.misses) == (1, 5, 3)
        j = rec.to_json()
        assert j["schema"] == ATTRIBUTION_SCHEMA
        json.dumps(j)
        assert bus.events("attr.replan")[0]["realized"] == \
            pytest.approx(3 / 5)

    def test_flush_resets_and_windows_decisions(self):
        bus = Telemetry()
        at = PlanAttribution(telemetry=bus)
        bus.event("ctl.force", knob="cache_capacity", value=128,
                  cause="demand", target=100)
        bus.event("serve.replan", round=1)     # not a decision: excluded
        at.note_batch(np.array([7]), np.array([False]))
        r1 = at.flush(rnd=1, plan=None, cause="cadence", knobs={},
                      capacity=64)
        assert [d["_name"] for d in r1.decisions] == ["ctl.force"]
        # the window advanced and the accumulators reset
        r2 = at.flush(rnd=2, plan=None, cause="cadence", knobs={},
                      capacity=64)
        assert r2.decisions == []
        assert r2.realized_miss_rate is None   # no batch in tenure 2
        assert r2.miss_rate_error is None
        assert len(at.records) == 2

    def test_no_owner_accounting_without_shards(self):
        at = PlanAttribution()                 # owner_shards=0
        at.note_batch(np.array([1, 2]), np.array([False, False]))
        rec = at.flush(rnd=0, plan=None, cause="x", knobs={}, capacity=8)
        assert rec.per_owner_misses == {}
        assert rec.misses == 2


class TestExportSurfaces:
    def test_reservoir_empty_is_well_defined(self):
        r = Reservoir()
        assert r.stats() == {"count": 0, "mean": 0.0, "p50": 0.0,
                             "p99": 0.0}
        assert r.percentile(99) == 0.0
        assert r.mean() == 0.0

    def test_snapshot_strictly_json_dumpable(self):
        bus = Telemetry()
        bus.inc("serve.requests", tenant="tenant münchen, a=b")
        bus.set("gauge.nan", float("nan"))
        bus.observe("lat", np.float64(1.5), shard=np.int64(3))
        bus.event("ev", arr=np.arange(3), flag=np.bool_(True))
        snap = bus.snapshot()
        json.dumps(snap)                       # must not raise
        assert snap["gauges"]["gauge.nan"] is None

    def test_prometheus_one_type_line_per_family(self):
        bus = Telemetry()
        bus.inc("serve.requests", tenant="a b")
        bus.inc("serve.requests", tenant="c\"d")
        bus.set("serve.miss_rate", 0.25)
        bus.observe("serve.latency", 2.0)
        bus.observe("serve.latency", 4.0)
        text = prometheus_text(bus)
        lines = text.strip().split("\n")
        assert lines.count("# TYPE serve_requests counter") == 1
        # one TYPE for the whole summary family — _count/_sum samples
        # must not get their own
        assert sum(1 for ln in lines if ln.startswith("# TYPE "
                                                      "serve_latency")) == 1
        assert 'serve_requests{tenant="a b"} 1.0' in lines
        assert 'serve_requests{tenant="c\\"d"} 1.0' in lines
        assert 'serve_latency{quantile="0.99"}' in text
        assert any(ln.startswith("serve_latency_count") for ln in lines)
        # snapshot-dict fallback renders too (best-effort labels)
        assert "serve_miss_rate 0.25" in prometheus_text(bus.snapshot())

    def test_jsonl_sink_roundtrip(self, tmp_path):
        bus = Telemetry()
        bus.inc("serve.requests", tenant="default")
        bus.event("ctl.force", knob="k", value=8, cause="demand")
        at = PlanAttribution(telemetry=bus)
        at.note_batch(np.array([3]), np.array([False]))
        at.flush(rnd=0, plan=None, cause="drift", knobs={}, capacity=4)
        path = str(tmp_path / "metrics.jsonl")
        with JsonlSink(path, flush_every=2) as sink:
            sink.write_bus(bus, label="test")
            sink.write_attribution(at.records)
        records = read_jsonl(path)
        kinds = [r["kind"] for r in records]
        assert kinds.count("snapshot") == 1
        assert kinds.count("attribution") == 1
        assert "event" in kinds
        snap = records[0]
        assert snap["schema"] == SCHEMA_VERSION
        attr = [r for r in records if r["kind"] == "attribution"][0]
        assert attr["schema"] == ATTRIBUTION_SCHEMA
        assert attr["realized_miss_rate"] == 1.0
        ev = [r for r in records if r["kind"] == "event"][0]
        assert ev["name"] == "ctl.force" and "event_seq" in ev

    def test_read_jsonl_rejects_corrupt_lines(self, tmp_path):
        p = tmp_path / "bad.jsonl"
        p.write_text('{"ok": 1}\nnot json\n')
        with pytest.raises(ValueError, match="bad.jsonl:2"):
            read_jsonl(str(p))


class TestTenantAccounting:
    def test_per_tenant_counters_and_latency(self):
        bus = Telemetry()
        sched = MicroBatchScheduler(batch_requests=4, keys_per_request=2,
                                    telemetry=bus)
        q = RequestQueue()
        q.enqueue(ServeRequest(0, np.array([1]), tenant="alpha"), now=0.0)
        q.enqueue(ServeRequest(1, np.array([2]), tenant="alpha"), now=0.0)
        q.enqueue(ServeRequest(2, np.array([3])), now=0.0)   # default
        batch = sched.admit(q)
        sched.note_served(batch.reqs, now=0.5)
        assert bus.counter_value("serve.requests", tenant="alpha") == 2
        assert bus.counter_value("serve.requests", tenant="default") == 1
        assert bus.latency("serve.latency", tenant="alpha").count == 2
        json.dumps(bus.snapshot())


def _traced_run(rounds=28, stream=None, tracer=None, **cfg_kw):
    rng = np.random.default_rng(0)
    table = rng.normal(size=(2048, 8)).astype(np.float32)
    kw = dict(vocab=2048, batch_requests=16, keys_per_request=8,
              cache_capacity=256, replan_every=6, trace=True)
    kw.update(cfg_kw)
    cfg = ServeConfig(**kw)
    if stream is None:
        stream = DriftingZipfStream(2048, kw["keys_per_request"],
                                    zipf_a=1.2,
                                    arrival_rate=kw["batch_requests"],
                                    scenario="rotate", rotate_every=10,
                                    seed=5)
    rt = ServingRuntime(table, cfg, tracer=tracer)
    res = rt.run(stream, rounds)
    return rt, res


class _Repeat:
    """The same 16 requests' keys every round: the queued horizon, and
    with it the plan's cache ids, never change after the first replan."""

    def __init__(self, keys_per_request=8, n=16):
        self.toks = np.random.default_rng(3).integers(
            0, 64, size=(n, keys_per_request))
        self.rid = 0

    def arrivals(self, rnd):
        out = [ServeRequest(self.rid + i, t) for i, t in
               enumerate(self.toks)]
        self.rid += len(out)
        return out


def _spans(rt, name):
    return [e for e in rt.tracer.events() if e["name"] == name]


def _inside(e, outer):
    return outer["t0_ns"] <= e["t0_ns"] and e["t1_ns"] <= outer["t1_ns"]


class TestTracedServe:
    def test_one_attribution_record_per_replan(self):
        rt, res = _traced_run()
        assert rt.attribution is not None
        assert len(rt.attribution.records) == res.replans >= 2
        # every measured tenure's realized rate is a proper rate
        for rec in rt.attribution.records:
            if rec.realized_miss_rate is not None:
                assert 0.0 <= rec.realized_miss_rate <= 1.0

    def test_request_spans_cover_every_served_request(self):
        rt, res = _traced_run()
        doc = rt.tracer.to_chrome()
        events = validate_chrome(doc)
        req_spans = [e for e in events if e["name"] == "serve.request"]
        assert len(req_spans) == rt.scheduler.n_served > 0
        rids = sorted(e["args"]["a"] for e in req_spans)
        assert rids == sorted(set(rids))       # each request exactly once
        phases = {e["name"] for e in events}
        assert {"serve.round", "serve.enqueue", "serve.plan",
                "serve.probe", "serve.dispatch"} <= phases
        assert rt.report().startswith("===")

    def test_untraced_runtime_records_nothing(self):
        rt, _ = _traced_run(rounds=8, trace=False)
        assert rt.attribution is None
        assert rt.tracer.count == 0


class TestPlanParts:
    """The replan's parts as spans (DESIGN.md §14), their profiler
    mirror, and the compile listener."""

    @pytest.mark.parametrize("refresh_every", [0, 2])
    def test_parts_lie_inside_their_replan_or_refresh_round(
            self, refresh_every):
        rt, res = _traced_run(refresh_every=refresh_every)
        plans = _spans(rt, "serve.plan")
        assert len(plans) == res.replans >= 2
        replan_rounds = sorted(p["a"] for p in plans)
        for name in ("serve.plan.solve", "serve.plan.probe_view",
                     "serve.refresh", "prefetch.stage"):
            for e in _spans(rt, name):
                owner = [p for p in plans if _inside(e, p)]
                if owner:
                    assert owner[0]["a"] == e["a"]
                    continue
                # a refresh round between replans
                assert name == "serve.refresh" and refresh_every > 0
                last = max(r for r in replan_rounds if r < e["a"])
                assert (e["a"] - last) % refresh_every == 0
        for p in plans:
            solves = [e for e in _spans(rt, "serve.plan.solve")
                      if _inside(e, p)]
            assert len(solves) == 1
        assert len(_spans(rt, "serve.refresh")) == res.refreshes
        if refresh_every:
            assert res.refreshes > res.replans

    @pytest.mark.parametrize("stream", ["rotate", "repeat"])
    def test_probe_view_is_built_exactly_when_the_cache_ids_change(
            self, monkeypatch, stream):
        from repro.pm.planner import IntentPlanner
        real = IntentPlanner.replan_from_queue
        ids = []

        def recording(self, *a, **k):
            plan = real(self, *a, **k)
            ids.append(np.array(plan.cache_ids))
            return plan

        monkeypatch.setattr(IntentPlanner, "replan_from_queue", recording)
        rt, res = _traced_run(
            stream=_Repeat() if stream == "repeat" else None)
        assert len(ids) == res.replans >= 3   # one solve call per replan
        changed = [i == 0 or not np.array_equal(ids[i], ids[i - 1])
                   for i in range(len(ids))]
        views = _spans(rt, "serve.plan.probe_view")
        built = [any(_inside(v, p) for v in views)
                 for p in _spans(rt, "serve.plan")]
        assert built == changed
        skipped = rt.telemetry.counter_value("serve.refresh_skipped")
        assert len(views) == res.replans - skipped
        if stream == "repeat":
            assert len(views) == 1
        else:
            assert len(views) == res.replans

    def test_disabled_tracer_enters_no_annotation(self, monkeypatch):
        import repro.obs.trace as trace_mod
        entered = []

        class Annotation:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                entered.append(self.name)

            def __exit__(self, *exc):
                return False

        monkeypatch.setattr(trace_mod, "_annotate", Annotation)
        off = SpanTracer(enabled=False)
        with off.span("serve.plan"):
            pass
        _traced_run(rounds=8, trace=False)
        assert entered == []
        on = SpanTracer()
        with on.span("serve.plan"):
            on.point("serve.requeue")
            on.record("serve.round", 0, 1)
        assert entered == ["serve.plan"]    # span() only, not records

    def test_profiler_capture_holds_the_phase_spans_on_its_clock(
            self, tmp_path):
        import jax
        from jax.profiler import ProfileData

        _traced_run(rounds=8)                  # compile outside the trace
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            rt, _ = _traced_run(rounds=16)
        finally:
            jax.profiler.stop_trace()
        (path,) = tmp_path.glob("**/*.xplane.pb")
        host = {}
        for plane in ProfileData.from_file(str(path)).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    host.setdefault(e.name, []).append(int(e.start_ns))
        for name in ("serve.plan", "serve.probe"):
            ours = [e["t0_ns"] for e in _spans(rt, name)]
            theirs = sorted(host.get(name, []))
            assert len(theirs) == len(ours) >= 2, name
            off = theirs[0] - ours[0]
            err = np.abs(np.asarray(theirs) - np.asarray(ours) - off)
            assert err.max() < 100_000, (name, err.max())

    def test_a_compile_inside_run_is_counted_and_spanned(self):
        import jax

        rt, _ = _traced_run(rounds=8)       # the runtime's shapes, warm
        stream = DriftingZipfStream(2048, 8, zipf_a=1.2, arrival_rate=16,
                                    scenario="steady", seed=9)
        forced = {}

        class Forcing:
            """Arrivals whose round 3 compiles a shape seen nowhere else."""

            def arrivals(self, rnd):
                if rnd == 3:
                    t0 = rt.tracer.now_ns()
                    jax.jit(lambda x: x * 3 + 1)(
                        np.zeros((7, 5, 3), np.float32))
                    forced["at"] = (t0, rt.tracer.now_ns())
                return stream.arrivals(rnd)

        heard = []

        def listen(event, secs, **_):
            if event in ("/jax/core/compile/backend_compile_duration",
                         "/jax/compilation_cache/"
                         "cache_retrieval_time_sec"):
                heard.append(secs)

        n0 = rt.telemetry.counter_value("jit.compiles")
        s0 = len(_spans(rt, "jit.compile"))
        jax.monitoring.register_event_duration_secs_listener(listen)
        try:
            rt.run(Forcing(), 8, warmup_backlog=0)
        finally:
            jax.monitoring.unregister_event_duration_listener(listen)
        spans = _spans(rt, "jit.compile")[s0:]
        assert len(heard) >= 1
        assert rt.telemetry.counter_value("jit.compiles") - n0 \
            == len(heard) == len(spans)
        a, b = forced["at"]
        assert sum(a <= e["t0_ns"] and e["t1_ns"] <= b
                   for e in spans) == 1

    def test_an_injected_tracer_does_not_turn_on_attribution(self):
        tr = SpanTracer()
        rt, res = _traced_run(rounds=8, trace=False, tracer=tr)
        assert rt.tracer is tr and tr.count > 0
        assert rt.attribution is None
        assert len(_spans(rt, "serve.plan")) == res.replans >= 1


class TestSharedBusThreading:
    def test_train_loop_shares_one_bus_and_traces_phases(self):
        from repro.configs.registry import get_config
        from repro.train.loop import LoopConfig, train_loop

        bus = Telemetry()
        tr = SpanTracer()
        cfg = get_config("smollm-135m", smoke=True)
        train_loop(cfg, LoopConfig(steps=6, batch=2, seq=16, pm=True,
                                   cache_capacity=64, n_shards=2,
                                   log_every=0, seed=3),
                   telemetry=bus, tracer=tr)
        # the planner published onto the SAME bus the loop was handed
        assert bus.events("plan.built")
        assert bus.gauge_value("plan.version") >= 1
        names = {e["name"] for e in tr.to_chrome()["traceEvents"]}
        assert {"train.signal", "train.plan", "train.refresh",
                "train.step"} <= names


class TestReportCLI:
    def test_render_sections(self):
        tr = SpanTracer()
        t = tr.now_ns()
        tr.record("serve.request", t, t + 2_000_000, a=0, b=1)
        tr.record("serve.plan", t, t + 500_000)
        recs = [{"kind": "attribution", "round": 3, "plan_version": 1,
                 "cause": "drift", "batches": 2, "tokens": 10,
                 "misses": 1, "predicted_miss_rate": 0.08,
                 "realized_miss_rate": 0.1,
                 "per_owner_misses": {"1": 1}, "top_keys": [[7, 1]],
                 "decisions": [{"_seq": 4, "_name": "ctl.force",
                                "knob": "k", "value": 8}]},
                {"kind": "event", "name": "ctl.trial", "event_seq": 9,
                 "fields": {"knob": "replan_every", "accepted": True}},
                {"kind": "snapshot", "counters": {"serve.requests": 5},
                 "latencies": {}}]
        text = render_report(tr.to_chrome()["traceEvents"], recs)
        assert "requests traced: 1" in text
        assert "miss attribution" in text and "0.1000" in text
        assert "shard1:1" in text
        assert "ctl.force" in text and "ctl.trial" in text
        assert "serve.requests=5" in text

    def test_cli_on_real_artifacts(self, tmp_path, capsys):
        rt, _ = _traced_run(rounds=16)
        trace = str(tmp_path / "trace.json")
        metrics = str(tmp_path / "metrics.jsonl")
        rt.tracer.dump(trace)
        with JsonlSink(metrics) as sink:
            sink.write_bus(rt.telemetry, label="test run")
            sink.write_attribution(rt.attribution.records)
        assert report_main([trace, metrics]) == 0
        out = capsys.readouterr().out
        assert "request latency (trace)" in out
        assert "miss attribution" in out

    def test_empty_inputs_still_render(self):
        assert "no spans or records" in render_report(None, None)
