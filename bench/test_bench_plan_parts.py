"""The readers of the replan's parts (`serve.plan.probe_view`,
`serve.plan.solve`, `serve.refresh`, each per replan): their arithmetic
on a synthetic context, and a tiny CPU run of the serving cell from
which all three read a number."""

import numpy as np
import pytest

import spec
from drivers import serve
from test_bench_cell import _run

PARTS = {"serve_max.probe_view_ms_per_replan": "serve.plan.probe_view",
         "serve_max.solve_ms_per_replan": "serve.plan.solve",
         "serve_max.refresh_ms_per_replan": "serve.refresh"}
MS = 1_000_000
WINDOW = (0, 100 * MS)


def _ctx(spans):
    none = np.zeros(0, np.int64)
    return serve.Ctx(kind="closed", setup_s=1.0, window_ns=WINDOW,
                     layer_window_ns=WINDOW, due_ns=none, enq_ns=none,
                     served_ns=none, keys_per_request=100,
                     tokens_per_batch=1600, row_bytes=512, spans=spans,
                     bus_log=[], peaks={})


@pytest.mark.parametrize("metric", sorted(PARTS))
def test_a_part_is_its_length_in_the_window_over_the_replans_there(metric):
    part = PARTS[metric]
    spans = [("serve.plan", 10 * MS, 20 * MS, 0, 0),
             (part, 11 * MS, 14 * MS, 0, 0),
             (part, 15 * MS, 16 * MS, 0, 0),
             ("serve.probe", 21 * MS, 22 * MS, 1, 0),
             ("serve.plan", 40 * MS, 50 * MS, 4, 0),
             (part, 41 * MS, 43 * MS, 4, 0),
             # after the window: in neither sum
             ("serve.plan", 150 * MS, 160 * MS, 8, 0),
             (part, 151 * MS, 159 * MS, 8, 0)]
    # (3 + 1 + 2) ms over two replans
    assert spec.load_reader(metric).read(_ctx(spans)) == pytest.approx(3.0)
    # the program has the span, the window holds none of it: zero
    assert spec.load_reader(metric).read(_ctx(spans[:1] + spans[-1:])) \
        == 0.0
    # no replan in the window: nothing to divide by
    assert spec.load_reader(metric).read(_ctx(spans[-2:])) is None


@pytest.mark.parametrize("metric", sorted(PARTS))
def test_a_part_reads_none_where_the_run_recorded_no_such_span(metric):
    spans = [("serve.plan", 10 * MS, 20 * MS, 0, 0),
             ("prefetch.stage", 12 * MS, 13 * MS, 0, 0),
             ("serve.probe", 21 * MS, 22 * MS, 1, 0)]
    assert spec.load_reader(metric).read(_ctx(spans)) is None


@pytest.fixture(scope="module")
def cpu_run():
    ctx, checks, _, _, _ = _run("zipf-max")
    assert spec.is_correct(checks), checks
    return ctx


def test_every_part_reads_a_number_from_a_cpu_run_of_the_cell(cpu_run):
    got = {m: spec.load_reader(m).read(cpu_run) for m in PARTS}
    assert all(isinstance(v, float) and v >= 0 for v in got.values()), got
    assert got["serve_max.solve_ms_per_replan"] > 0
    assert got["serve_max.refresh_ms_per_replan"] > 0


def test_the_parts_add_up_to_no_more_than_the_replan(cpu_run):
    plan = spec.load_reader("serve_max.plan_ms_per_replan").read(cpu_run)
    parts = sum(spec.load_reader(m).read(cpu_run) for m in PARTS)
    assert 0 < parts <= plan
