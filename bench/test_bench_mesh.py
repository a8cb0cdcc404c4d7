"""The four-chip cell's configuration driven end to end on four host
devices, and the arithmetic of its per-layer readers.

`dlrm-t20-40m` is loaded by name and cut to a small row count (a
multiple of 4 owners x 8 rows) with the jnp data path; everything else
runs as on the chips: the table row-sharded over the mesh, the routed
miss gathers and refreshes, the comparison with the plain reference."""

import json
import os
import subprocess
import sys

import pytest

import devtrace
import spec
from drivers import serve

CELL = "serve.dlrm-t20-40m.zipf-max"
SEED = 2**31 + 29

MESH_RUN = """
import json, sys, time
sys.path[:0] = [{here!r}, {src!r}]
import spec
from drivers import serve
bench = spec.load_benchmark()
cell = spec.find(bench["workloads"], {cell!r}, "workload")
cfg = spec.load_config(bench, cell["config"])
cfg = dict(cfg, num_embeddings=4096, serve=dict(cfg["serve"], kernel=False))
t = dict(spec.load_traffic(cell["traffic"]), warmup_s=0.5,
         prime_requests=32, outstanding=48, drain_limit_s=3)
ctx, checks, att, failed, _ = serve.run_cell(
    cfg, t, {seed}, 1.0, False, time.perf_counter_ns(),
    spec.peaks("TPU v5 lite"), cell["chips"])
# what a traced window on four chips gives the device readers
per_chip = {{"all-gather-start.1": 0.002, "embed_gather.1": 0.001,
            "pm_combine.1": 0.004}}
ctx.trace = {{"busy_s": 0.01, "window_s": 1.0, "devices": 4,
             "op_s": {{k: 4 * v for k, v in per_chip.items()}}}}
metrics = [m["name"] for m in spec.metrics_for(bench, {cell!r}, True)]
read = {{m: spec.load_reader(m).read(ctx) for m in metrics}}
print(json.dumps({{"correct": spec.is_correct(checks), "failed": failed,
                  "in_window": ctx.info["requests_in_window"],
                  "compiles": ctx.info["compiles_in_window"],
                  "read": read}}))
"""


def test_the_whole_table_config_serves_exactly_on_four_devices():
    """The driver shards the table over the cell's four chips and the
    mesh serves it, correct; every per-layer reader of the cell reads a
    number, the new ones included."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = MESH_RUN.format(here=spec.HERE, src=os.path.join(spec.ROOT, "src"),
                           cell=CELL, seed=SEED)
    p = subprocess.run([sys.executable, "-c", code], cwd=spec.ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] and out["failed"] == 0 and out["in_window"] > 10
    assert out["compiles"] == 0
    new = {n for n in out["read"] if n.startswith("serve_mesh.")}
    assert len(new) == 9
    assert all(isinstance(v, float) for v in out["read"].values()), \
        out["read"]
    # every chip combines the whole batch: bytes over the summed seconds
    assert 0 < out["read"]["serve_mesh.row_kernels_roofline"] < 100
    assert out["read"]["serve_mesh.collective_ms_per_batch"] > 0


def test_the_cell_is_on_four_chips_and_reports_lookups_per_s():
    bench = spec.load_benchmark()
    cell = spec.find(bench["workloads"], CELL, "workload")
    config = spec.load_config(bench, cell["config"])
    assert cell["chips"] == 4 == config["row_shards"]
    assert config["serve"]["collective"] == "mesh"
    assert config["num_embeddings"] == config["published_num_embeddings"]
    assert config["num_embeddings"] % (4 * 8) == 0
    assert [m["name"] for m in spec.metrics_for(bench, CELL, False)] == \
        ["setup_s", "serve_lookups_per_s"]


def _trace(planes, combine_s, gather_s):
    """A reduced trace of ``planes`` chips, each running the combine and
    the gather for the given device seconds inside a 1 s window."""
    anchors = {devtrace.OPEN: 0, devtrace.CLOSE: 1_000_000_000}
    rows = [["/host:CPU", "python", n, t, 10] for n, t in anchors.items()]
    for k in range(planes):
        dev = f"{devtrace.DEVICE_PREFIX}{k}"
        rows += [[dev, devtrace.OPS_LINE, "pm_combine.1", 1000,
                  int(combine_s * 1e9)],
                 [dev, devtrace.OPS_LINE, "embed_gather.1", 500_000_000,
                  int(gather_s * 1e9)]]
    return devtrace.reduce_trace(rows, anchors)


@pytest.mark.parametrize("residual_per_chip", [0, 700])
def test_the_mesh_roofline_of_four_chips_equals_one_chip_doing_its_share(
        residual_per_chip):
    """Four chips, each combining the same whole batch and gathering its
    share of the residual misses, read the share one chip reads for that
    same work (and the one-chip reader of the one-chip cell agrees)."""
    batches = 40
    spans = [("serve.dispatch", 10 + i, 11 + i, 0, 0)
             for i in range(batches)]

    def ctx(trace, residual):
        return serve.Ctx(
            kind="closed", setup_s=1.0, window_ns=(0, 10**9),
            layer_window_ns=(0, 10**9), due_ns=None, enq_ns=None,
            served_ns=None, keys_per_request=100, tokens_per_batch=1600,
            row_bytes=512, spans=spans,
            bus_log=[(5, "serve.prefetch_stale", float(residual))],
            peaks=spec.peaks("TPU v5 lite"), trace=trace)

    mesh = spec.load_reader("serve_mesh.row_kernels_roofline")
    one = mesh.read(ctx(_trace(1, 0.02, 0.001), residual_per_chip))
    four = mesh.read(ctx(_trace(4, 0.02, 0.001), 4 * residual_per_chip))
    assert four == pytest.approx(one, rel=1e-12)
    assert 0 < four < 100
    single = spec.load_reader("serve_max.row_kernels_roofline")
    assert single.read(ctx(_trace(1, 0.02, 0.001), residual_per_chip)) \
        == pytest.approx(one, rel=1e-12)
