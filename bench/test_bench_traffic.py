"""Traffic, discovery and latency accounting of the benchmark harness.

CPU only; nothing here loads a TPU library."""

import json
import os
import time

import numpy as np
import pytest

import readers
import spec
import traffic
from drivers import serve
from repro.data.pipeline import SyntheticCorpus
from repro.serve.requests import ServeRequest  # noqa: F401  (warm import)


@pytest.mark.parametrize("rows,a,seed", [(5000, 1.1, 3),
                                         (1 << 16, 1.1, 2**31 + 9),
                                         (777, 0.8, 0)])
def test_zipf_keys_are_synthetic_corpus_draws(rows, a, seed):
    """Same seed, same truncated Zipf, same draws as SyntheticCorpus."""
    want = SyntheticCorpus(rows, zipf_a=a, seed=seed).tokens((4, 1000))
    got = traffic.ZipfKeys(rows, a, seed).draw(4000).reshape(4, 1000)
    np.testing.assert_array_equal(got, want)


def test_zipf_keys_follow_the_law():
    rows, n = 1000, 200_000
    k = traffic.ZipfKeys(rows, 1.1, 5)
    counts = np.bincount(k.draw(n), minlength=rows)
    p = np.arange(1, rows + 1, dtype=float) ** -1.1
    p /= p.sum()
    head = counts[k.perm[:5]] / n          # the five hottest ranks
    np.testing.assert_allclose(head, p[:5], rtol=0.03)


def test_gaps_repeat_for_a_seed_and_keep_the_rate():
    a = traffic.exponential_gaps(50.0, 4000, 2**31 + 1)
    b = traffic.exponential_gaps(50.0, 4000, 2**31 + 1)
    c = traffic.exponential_gaps(50.0, 4000, 7)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    np.testing.assert_array_equal(np.sort(a), np.sort(c))  # same set
    assert abs(a.mean() * 50.0 - 1.0) < 0.01
    # exponential: the coefficient of variation is 1
    assert abs(a.std() / a.mean() - 1.0) < 0.05


def test_request_keys_repeat_for_a_seed():
    t = {"zipf_a": 1.1, "keys_per_request": 8}
    a = traffic.request_keys(3000, t, 50, 11)
    np.testing.assert_array_equal(a, traffic.request_keys(3000, t, 50, 11))
    assert a.shape == (50, 8) and a.dtype == np.int32
    assert a.min() >= 0 and a.max() < 3000


def test_open_loop_hands_out_what_is_due_with_its_due_time():
    keys = np.arange(40, dtype=np.int32).reshape(10, 4)
    reqs = traffic.Requests(keys)
    offs = np.arange(8, dtype=float) * 5.0          # due at 0, 5, .. 35 s
    loop = traffic.OpenLoop(reqs, offs, first=2)
    origin = time.perf_counter_ns() - int(10.5e9)   # 10.5 s ago
    loop.start(origin)
    got = loop.arrivals(0)
    assert [r.rid for r in got] == [2, 3, 4]        # due at 0, 5, 10 s
    assert loop.arrivals(1) == []
    np.testing.assert_array_equal(
        reqs.due_ns[2:5], origin + np.array([0, 5, 10]) * 1_000_000_000)
    np.testing.assert_array_equal(got[1].keys, keys[3])


def test_closed_loop_keeps_its_requests_outstanding():
    from repro.obs.telemetry import Telemetry
    bus = Telemetry()
    reqs = traffic.Requests(np.zeros((20, 2), np.int32))
    loop = traffic.ClosedLoop(reqs, 5, bus)
    assert len(loop.arrivals(0)) == 5
    assert loop.arrivals(1) == []
    bus.inc("serve.requests", 3, tenant="default")
    assert [r.rid for r in loop.arrivals(2)] == [5, 6, 7]
    bus.inc("serve.requests", 20, tenant="default")
    # past the pool's 20 rows the loop recycles it; it never runs dry
    assert [r.rid for r in loop.arrivals(3)] == list(range(8, 28))
    assert loop.pool_cycles == 1


def test_a_closed_loop_recycles_its_pool_row_by_row():
    """40 releases over a pool of 8 requests, after 2 primed ones: rids
    unique and increasing, keys cycling through the pool's rows, a due
    stamp for every release."""
    from repro.obs.telemetry import Telemetry
    bus = Telemetry()
    prime, pool = 2, 8
    keys = np.arange(2 * (prime + pool), dtype=np.int32).reshape(-1, 2)
    reqs = traffic.Requests(keys, pool_from=prime)
    loop = traffic.ClosedLoop(reqs, 4, bus)
    got = []
    for rnd in range(10):
        got += loop.arrivals(rnd)
        bus.inc("serve.requests", 4, tenant="default")
    rids = [r.rid for r in got]
    assert rids == list(range(prime, prime + 40))
    rows = prime + np.arange(40) % pool
    np.testing.assert_array_equal(np.stack([r.keys for r in got]),
                                  keys[rows])
    assert [reqs.row_of(i) for i in rids] == rows.tolist()
    assert reqs.row_of(1) == 1                  # the primed rows stay
    assert (reqs.due_ns[prime:prime + 40] > 0).all()
    assert reqs.issued == prime + 40 and len(reqs.req) >= reqs.issued
    assert loop.pool_cycles == 4                # wrapped after 8, .., 32


def _ctx(due, enq, served):
    return serve.Ctx(kind="open", setup_s=1.0, window_ns=(0, int(2e9)),
                     layer_window_ns=(0, int(2e9)),
                     due_ns=np.asarray(due, np.int64),
                     enq_ns=np.asarray(enq, np.int64),
                     served_ns=np.asarray(served, np.int64),
                     keys_per_request=64, tokens_per_batch=1024,
                     row_bytes=512, spans=[], bus_log=[], peaks={})


def test_latency_runs_from_the_due_time():
    ms = 1_000_000
    due = np.arange(100) * 10 * ms
    enq = due + 5 * ms                   # admission waited 5 ms
    served = due + (np.arange(100) + 1) * ms   # 1 .. 100 ms after due
    c = _ctx(due, enq, served)
    assert readers.percentile_ms(c, 50) == pytest.approx(50.5)
    assert readers.percentile_ms(c, 95) == pytest.approx(95.05)
    assert readers.percentile_ms(c, 50, since="enqueue") == \
        pytest.approx(45.5)
    assert readers.admit_wait_ms(c, 95) == pytest.approx(5.0)
    assert readers.lookups_per_s(c) == pytest.approx(100 * 64 / 2.0)


def test_unserved_requests_leave_the_percentiles_to_the_served():
    ms = 1_000_000
    c = _ctx([0, 0, 0], [0, 0, 0], [10 * ms, 20 * ms, -1])
    assert readers.percentile_ms(c, 50) == pytest.approx(15.0)
    assert readers.lookups_per_s(c) == pytest.approx(2 * 64 / 2.0)


def test_every_cell_finds_its_config_traffic_and_readers():
    bench = spec.load_benchmark()
    assert bench["paths"] == ["bench"]
    for cell in bench["workloads"]:
        config = spec.load_config(bench, cell["config"])
        assert config["name"] == cell["config"]
        assert callable(spec.load_driver(config["kind"]).run_cell)
        t = spec.load_traffic(cell["traffic"])
        assert {"kind", "zipf_a", "keys_per_request"} <= set(t)
        e2e = {m["name"] for m in spec.metrics_for(bench, cell["name"],
                                                   False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        layers = spec.metrics_for(bench, cell["name"], True)
        assert layers and all(m["moves"] in e2e for m in layers)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(spec.load_reader(m["name"]).read)


def test_a_kind_without_a_driver_is_an_error():
    with pytest.raises(KeyError, match="bench/drivers/train.py"):
        spec.load_driver("train")


def test_metrics_without_workloads_go_where_what_they_move_is():
    bench = {"end_to_end": [
        {"name": "setup_s"},
        {"name": "a_ms", "workloads": ["x"]}],
        "per_layer": [{"name": "l1", "moves": "a_ms"},
                      {"name": "l2", "moves": "setup_s",
                       "workloads": ["y"]}]}
    assert [m["name"] for m in spec.metrics_for(bench, "x", False)] == \
        ["setup_s", "a_ms"]
    assert [m["name"] for m in spec.metrics_for(bench, "x", True)] == ["l1"]
    assert [m["name"] for m in spec.metrics_for(bench, "y", True)] == ["l2"]


def test_a_reader_that_finds_nothing_is_left_out(tmp_path):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "m.none.py").write_text(
        "def read(ctx):\n    return None\n")
    assert spec.load_reader("m.none", here=str(tmp_path)).read(None) is None


def test_peaks_are_known_by_device_kind_and_unknown_is_an_error():
    p = spec.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        spec.peaks("TPU v99")
    with open(os.path.join(spec.HERE, "peaks.json")) as f:
        assert "Google Cloud" in json.load(f)["source"]


def test_pinned_knob_check_names_every_moved_knob():
    pinned = {"replan_every": 4, "batch_requests": 16}
    assert serve.moved_knobs(pinned, {"replan_every": 4,
                                      "batch_requests": 16}) == {}
    assert serve.moved_knobs(pinned, {"replan_every": 8,
                                      "batch_requests": 16}) == \
        {"replan_every": 8}
    assert not spec.is_correct({"knobs_moved": (1, 0)})
    assert spec.is_correct({"knobs_moved": (0, 0), "gap": (0.0, 0.0)})
