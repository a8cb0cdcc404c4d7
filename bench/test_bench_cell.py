"""One serving cell driven end to end on the CPU at a tiny size.

The harness's look for a chip is skipped (`serve.run_cell` is called
directly); everything after it runs as on the chip: table and traffic
from the seed, prime, warm-up, window, and the comparison with the
plain reference.  Broken underneath, the run must come out not correct:
an answer altered where it is produced, the table served in bfloat16
(the control), a pinned knob that moved."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import spec
from drivers import serve

ROWS = 4096
SEED = 2**31 + 17


def _cell(kind: str):
    bench = spec.load_benchmark()
    config = spec.load_config(bench, "dlrm-t20-10m")
    config = dict(config, num_embeddings=ROWS,
                  serve=dict(config["serve"], kernel=False))
    t = dict(spec.load_traffic(kind), warmup_s=0.5, prime_requests=32)
    if t["kind"] == "serve_open":
        t.update(rate_rps=40.0, drain_limit_s=5)
    else:
        t.update(outstanding=48, pool_requests=200_000, drain_limit_s=3)
    return config, t


def _run(kind="zipf-rate", dtype=None, seconds=1.0, **traffic):
    config, t = _cell(kind)
    t.update(traffic)
    return serve.run_cell(config, t, SEED, seconds, False,
                          time.perf_counter_ns(),
                          spec.peaks("TPU v5 lite"), 1, dtype=dtype)


@pytest.mark.parametrize("kind", ["zipf-rate", "zipf-max"])
def test_the_program_serves_every_row_of_the_reference(kind):
    ctx, checks, attempted, failed, _ = _run(kind)
    assert spec.is_correct(checks), checks
    assert attempted > 10 and failed == 0
    assert ctx.info["knobs_moved"] == "none"
    assert (ctx.served_ns >= ctx.due_ns).all()
    assert ctx.setup_s > 0


POOL = 64       # a pool this small wraps many times in a 1 s CPU run


def test_a_closed_loop_that_outlasts_its_pool_recycles_it_and_is_correct():
    ctx, checks, attempted, failed, _ = _run("zipf-max", pool_requests=POOL)
    assert spec.is_correct(checks), checks
    assert ctx.info["pool_cycles"] >= 2
    # every request served from the window's open on is compared
    served = sum(1 for n, _, t1, _, _ in ctx.spans
                 if n == "serve.request" and t1 >= ctx.window_ns[0])
    assert ctx.info["requests_compared"] == served == attempted > 2 * POOL
    assert failed == 0


def test_a_wrong_answer_after_the_wrap_is_not_correct(monkeypatch):
    """The program's answer altered for one pool row, in the releases
    that recycle it only: the run is not correct."""
    from repro.serve.runtime import ServingRuntime
    real = ServingRuntime.run
    prime = _cell("zipf-max")[1]["prime_requests"]

    def altered(self, *a, **k):
        res = real(self, *a, **k)
        for rid, out in res.outputs.items():
            if rid >= prime + POOL and (rid - prime) % POOL == 3:
                res.outputs[rid] = out + 1.0
        return res

    monkeypatch.setattr(ServingRuntime, "run", altered)
    ctx, checks, _, failed, _ = _run("zipf-max", pool_requests=POOL)
    assert ctx.info["pool_cycles"] >= 2
    assert not spec.is_correct(checks)
    assert 0 < checks["requests_wrong"][0] == failed
    assert checks["requests_wrong"][0] <= ctx.info["pool_cycles"]


def test_the_control_in_bfloat16_is_not_correct():
    _, checks, _, failed, _ = _run(dtype="bfloat16")
    assert not spec.is_correct(checks)
    assert checks["row_gap_max"][0] > 1e-4 and failed > 0


def test_an_answer_altered_where_it_is_produced_is_not_correct(
        monkeypatch):
    import repro.serve.runtime as rt_mod
    real = rt_mod.planned_serve_lookup

    def altered(*a, **k):
        out = real(*a, **k)
        return out.at[3].add(1.0)

    monkeypatch.setattr(rt_mod, "planned_serve_lookup", altered)
    _, checks, _, failed, _ = _run()
    assert not spec.is_correct(checks)
    assert checks["requests_wrong"][0] > 0 and failed > 0


@pytest.mark.parametrize("kind", ["zipf-rate", "zipf-max"])
def test_half_of_each_batch_left_out_is_not_correct(monkeypatch, kind):
    """Requests popped into a batch and then dropped, half of them, count
    as unserved: in the closed loop too, where the lost requests hold
    the loop's places and the window may serve none."""
    from repro.serve.requests import RequestQueue
    real = RequestQueue.pop_batch

    def half(self, n):         # every odd request id is popped and lost
        return [r for r in real(self, n) if r.rid % 2 == 0]

    monkeypatch.setattr(RequestQueue, "pop_batch", half)
    _, checks, attempted, failed, _ = _run(kind)
    assert not spec.is_correct(checks)
    assert checks["requests_unserved"][0] > 0 and failed > 0


def test_a_knob_that_moved_fails_the_run(monkeypatch):
    from repro.serve.runtime import ServingRuntime
    real = ServingRuntime._controller_step

    def drift(self, rnd, res):
        real(self, rnd, res)
        self.replan_every = 8

    monkeypatch.setattr(ServingRuntime, "_controller_step", drift)
    ctx, checks, _, _, _ = _run()
    assert checks["knobs_moved"][0] == 1
    assert not spec.is_correct(checks)


MESH_RUN = """
import sys, time
sys.path[:0] = [{here!r}, {src!r}]
import spec
from drivers import serve
cfg = spec.load_config(spec.load_benchmark(), "dlrm-t20-10m")
cfg = dict(cfg, num_embeddings=4096,
           serve=dict(cfg["serve"], kernel=False, collective="mesh"))
t = dict(spec.load_traffic("zipf-max"), warmup_s=0.5, prime_requests=32,
         outstanding=48, drain_limit_s=3)
ctx, checks, att, failed, _ = serve.run_cell(
    cfg, t, {seed}, 1.0, False, time.perf_counter_ns(),
    spec.peaks("TPU v5 lite"), 4)
print(spec.is_correct(checks), att, failed, ctx.info["requests_in_window"])
"""


def test_a_cell_on_four_chips_serves_a_table_sharded_over_them():
    """The driver builds the table row-sharded over the cell's chips and
    the program's mesh collective serves it, with no edit: four host
    devices stand in for the chips."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = MESH_RUN.format(here=spec.HERE, seed=SEED,
                           src=os.path.join(spec.ROOT, "src"))
    p = subprocess.run(
        [sys.executable, "-c", code],
        cwd=spec.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    correct, att, failed, in_window = p.stdout.split()[-4:]
    assert correct == "True" and failed == "0" and int(in_window) > 10


def test_without_a_tpu_the_command_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(spec.HERE, "run.py"), "--workload",
         "serve.dlrm-t20-10m.zipf-max", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=spec.ROOT, env=env, capture_output=True,
        text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs 1 TPU" in p.stderr


def test_the_result_line_ends_with_the_checks():
    import run
    ms = 1_000_000
    ctx = serve.Ctx(kind="closed", setup_s=2.5, window_ns=(0, int(2e9)),
                    layer_window_ns=(0, int(2e9)),
                    due_ns=np.arange(10) * ms, enq_ns=np.arange(10) * ms,
                    served_ns=np.arange(10) * ms + 7 * ms,
                    keys_per_request=64, tokens_per_batch=1024,
                    row_bytes=512, spans=[], bus_log=[], peaks={})
    checks = {"row_gap_max": (0.0, 0.0), "requests_wrong": (0, 0)}
    out = run.result_line(ctx, checks, 10, 0, 123, spec.load_benchmark(),
                          "serve.dlrm-t20-10m.zipf-max", False,
                          {"platform": "tpu", "kind": "TPU v5 lite",
                           "count": 1})
    line = json.loads(json.dumps(out))
    assert list(line)[-1] == "checks"
    assert line["correct"] is True
    assert set(line["metrics"]) == {"setup_s", "serve_lookups_per_s"}
    assert line["metrics"]["serve_lookups_per_s"]["value"] == \
        pytest.approx(10 * 64 / 2.0)
    assert line["device"]["memory_peak_bytes"] == 123
