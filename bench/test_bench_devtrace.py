"""The reduction from a profiler trace to device busy time, operation
time and named idle gaps.  CPU only; reads flattened trace rows."""

import json
import os

import numpy as np
import pytest

import devtrace
import readers
from drivers import serve

DEV = "/device:TPU:0"
HOST = "/host:CPU"
OFF = 5_000_000          # trace clock minus perf_counter_ns


def _rows(ops, anchors):
    rows = [[HOST, "python", n, t + OFF, 1000] for n, t in anchors.items()]
    rows += [[DEV, devtrace.OPS_LINE, n, s + OFF, d] for n, s, d in ops]
    return rows


def test_busy_time_is_the_union_of_operations_inside_the_window():
    anchors = {devtrace.OPEN: 1000, devtrace.CLOSE: 11000}
    ops = [("fusion", 500, 1000),         # clipped to [1000, 1500)
           ("pm_combine", 2000, 1000),    # [2000, 3000)
           ("copy", 2500, 1000),          # overlaps: union to 3500
           ("embed_gather", 9000, 4000)]  # clipped to [9000, 11000)
    s = devtrace.reduce_trace(_rows(ops, anchors), anchors)
    assert s["window_s"] == pytest.approx(10000 / 1e9)
    assert s["busy_s"] == pytest.approx((500 + 1500 + 2000) / 1e9)
    assert s["op_s"]["copy"] == pytest.approx(1000 / 1e9)
    assert s["op_s"]["fusion"] == pytest.approx(500 / 1e9)
    assert [g[1] for g in s["idle_gaps"]] == pytest.approx(
        [5500 / 1e9, 500 / 1e9])          # [3500, 9000), [1500, 2000)
    assert devtrace.op_seconds(s, ["pm_combine", "embed_gather"]) == \
        pytest.approx(3000 / 1e9)


def test_idle_gaps_are_named_by_the_span_the_host_was_in():
    anchors = {devtrace.OPEN: 0, devtrace.CLOSE: 10_000}
    ops = [("a", 0, 1000), ("b", 4000, 1000), ("c", 9000, 1000)]
    spans = [("serve.round", 0, 10_000),     # an envelope: never a name
             ("serve.plan", 1000, 3600),     # an envelope too
             ("serve.plan.probe_view", 1000, 3000),  # most of [1000, 4000)
             ("serve.plan.solve", 3000, 3500),
             ("serve.probe", 3500, 4000),
             ("serve.enqueue", 5200, 5300)]  # gap [5000, 9000): 100 ns
    s = devtrace.reduce_trace(_rows(ops, anchors), anchors, spans)
    assert s["idle_gaps"] == [["serve.enqueue", 4000 / 1e9],
                              ["serve.plan.probe_view", 3000 / 1e9]]
    s = devtrace.reduce_trace(_rows(ops, anchors), anchors, spans[:1])
    assert s["idle_gaps"][0][0] == devtrace.NO_SPAN


def test_a_gap_is_split_by_the_spans_that_cover_it():
    ms = 1_000_000
    spans = [("serve.round", 0, 10 * ms),     # an envelope: left out
             ("serve.plan", 1 * ms, 4 * ms),      # an envelope: left out
             ("serve.plan.probe_view", 1 * ms, 4 * ms),
             ("serve.served", 3 * ms, 6 * ms),
             ("serve.plan", 9 * ms, 12 * ms),
             ("serve.plan.probe_view", 9 * ms, 12 * ms)]
    cover = devtrace.gap_cover(2 * ms, 10 * ms, spans)
    assert cover == {"serve.plan.probe_view": 3.0, "serve.served": 3.0,
                     devtrace.NO_SPAN: 3.0}
    assert devtrace.gap_cover(0, ms, spans) == {devtrace.NO_SPAN: 1.0}


def test_a_trace_without_anchors_or_device_work_is_refused():
    anchors = {devtrace.OPEN: 0, devtrace.CLOSE: 100}
    with pytest.raises(RuntimeError, match="anchors"):
        devtrace.reduce_trace([[DEV, devtrace.OPS_LINE, "a", 0, 5]], anchors)
    with pytest.raises(RuntimeError, match="no device operation"):
        devtrace.reduce_trace(_rows([], anchors), anchors)


def test_the_recorded_chip_trace_reduces_to_its_replans():
    """2.5 s of a traced zipf-max window on a TPU v5 lite: the device is
    busy under 1% of the time, mostly in the row combine kernel, and every
    long idle gap is a replan, named by the part the host was in (the
    probe view's build), never by the `serve.plan` envelope."""
    with open(os.path.join(os.path.dirname(__file__),
                           "recorded_trace.json")) as f:
        rec = json.load(f)
    s = devtrace.reduce_trace(rec["rows"], rec["anchors"],
                              [tuple(x) for x in rec["spans"]])
    assert s["window_s"] == pytest.approx(2.5)
    assert s["devices"] == 1
    assert 0.001 < s["busy_s"] / s["window_s"] < 0.01
    # busy time never exceeds the sum of the operations' clipped times
    assert s["busy_s"] <= sum(s["op_s"].values()) + 1e-9
    assert s["device_ops"][0][0] == "_pm_combine.1"
    gaps = s["idle_gaps"]
    assert len(gaps) == 10 and all(g[1] > 0 for g in gaps)
    assert [g[0] for g in gaps[:5]] == ["serve.plan.probe_view"] * 5
    assert all(g[1] > 0.3 for g in gaps[:5])
    assert "serve.plan" not in {g[0] for g in gaps}
    assert sum(g[1] for g in gaps) <= s["window_s"] - s["busy_s"] + 1e-9
    assert devtrace.op_seconds(s, ["pm_combine", "embed_gather"]) > 0


def test_kernel_names_come_from_the_hlo_text():
    assert devtrace.op_name(
        "%_pm_combine.1 = f32[1024,128]{1,0} custom-call(...)") == \
        "_pm_combine.1"
    assert devtrace.op_name("fusion.3") == "fusion.3"


def test_roofline_counts_the_rows_the_lookup_needs():
    ctx = serve.Ctx(
        kind="closed", setup_s=1.0, window_ns=(0, 10), layer_window_ns=(0, 10),
        due_ns=np.zeros(0, np.int64), enq_ns=np.zeros(0, np.int64),
        served_ns=np.zeros(0, np.int64), keys_per_request=64,
        tokens_per_batch=1024, row_bytes=512,
        spans=[("serve.dispatch", 1, 2, 0, 0), ("serve.dispatch", 3, 4, 0, 0)],
        bus_log=[(1, "serve.prefetch_stale", 48.0)],
        peaks={"hbm_bytes_per_s": 819e9},
        trace={"op_s": {"_pm_combine.1": 1e-3, "_embed_gather.1": 1e-3,
                        "pad.4": 5.0}, "busy_s": 0.01, "window_s": 1.0})
    need = 2 * (2 * 1024 + 48) * 512 / 819e9
    assert readers.row_kernels_roofline_pct(
        ctx, ("embed_gather", "pm_combine")) == pytest.approx(
            100 * need / 2e-3)
    assert readers.device_ms_per_batch(ctx) == pytest.approx(5.0)
    assert readers.idle_share_pct(ctx) == pytest.approx(99.0)
    ctx.trace = None
    assert readers.row_kernels_roofline_pct(ctx, ("pm_combine",)) is None


def test_replans_are_counted_per_dispatched_batch():
    ctx = serve.Ctx(
        kind="open", setup_s=1.0, window_ns=(0, 100),
        layer_window_ns=(0, 100), due_ns=np.zeros(0, np.int64),
        enq_ns=np.zeros(0, np.int64), served_ns=np.zeros(0, np.int64),
        keys_per_request=64, tokens_per_batch=1024, row_bytes=512,
        spans=[("serve.dispatch", t, t + 1, 0, 0) for t in (5, 15, 25, 35)]
        + [("serve.dispatch", 150, 151, 0, 0)],
        bus_log=[(4, "serve.replans", 1.0), (30, "serve.replans", 1.0),
                 (120, "serve.replans", 1.0)], peaks={})
    assert readers.replans_per_batch(ctx) == pytest.approx(0.5)
