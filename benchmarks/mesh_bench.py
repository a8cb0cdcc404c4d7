"""Mesh-real collective benchmark: managed vs plain lookup over the
`shard_map` psum data path (DESIGN.md §10), on a multi-device mesh.

This is the acceptance measurement for the collective-backend layer: with
the table vocab-sharded over a real ``("model",)`` mesh, the managed path
moves only the compact ``(M+1, D)`` intent-planned miss buffer through
the psum while the plain vocab-parallel baseline moves every token's row
— the ``(T, D)`` dense partial-sum.  Reported per Zipf skew:

  * device time of the managed data path (`planned_serve_lookup` over
    `MeshBackend`; the index stage runs at admission, host-side) vs the
    plain dense lookup (`plain_serve_lookup` over the same mesh);
  * the wire story: rows through the collective, managed vs plain;
  * the training closure: fwd+bwd time of `pm_lookup` (psum forward,
    psum_scatter backward) vs a dense lookup's gather/scatter;
  * the ``fused`` arm (ISSUE 6): the routed fused managed step —
    destination-compacted `all_to_all` miss gather + on-shard sparse
    AdaGrad (`MeshBackend.gather_rows_routed` / `update_rows`, donated
    buffers) — paired call-for-call against a faithful replica of the
    PR-4 mesh step (replicated psum gather, dense ``(V, D)`` partial +
    psum_scatter backward, dense optimizer sweep over the sharded
    table).  Both sides run the pure-jnp row math: on this CPU container
    interpret-mode Pallas timings are meaningless, and the jnp path
    isolates exactly what the PR changed — collective layout and memory
    traffic.

On an accelerator host the mesh spans the devices present, in this one
process (a chip belongs to the process that touched JAX first).  On the
CPU backend it needs 8 virtual devices; a CPU process with fewer (e.g.
launched from ``benchmarks.run``) re-execs itself in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` — the flag only
takes effect before jax initializes.  Writes ``BENCH_mesh.json`` at the
repo root next to the other BENCH_* trajectories.

CLI:
  python -m benchmarks.mesh_bench [--quick]
  python -m benchmarks.mesh_bench --check-baseline BENCH_mesh.json
  python -m benchmarks.mesh_bench --pipeline --check-baseline BENCH_mesh.json

``--check-baseline`` is the CI regression guard for the fused arm: it
re-measures the quick skews and FAILS (exit 1) if the fused step's
median regressed more than 15% against the committed baseline; with
``--pipeline`` it guards the §15 pipelined-refresh arm instead.  The
comparison is normalized through the paired legacy replica (current
fused/legacy ratio vs the committed one), so absolute CPU-speed
differences between CI hosts don't trip it while a real routed-path
regression does.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import List

import numpy as np

_OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                    "BENCH_mesh.json")

N_DEV = 8                # virtual devices of a CPU host-platform run
V, D = 32768, 256
B, K = 16, 256           # T = 4096 tokens per batch
C = 4096                 # replica-cache capacity (holds the Zipf head)
ITERS = 20
FUSED_ITERS = 9          # paired-median iters of the fused-step arm
SKEWS_FULL = (1.0, 1.1, 1.5)
SKEWS_QUICK = (1.0, 1.1)
REGRESSION_TOL = 1.15    # CI guard: >15% normalized regression fails
# §15 pipeline arm: refresh-every-round replica sync over the mesh —
# synchronous full C-row psum re-gather vs the routed delta re-gather
# (touched ∩ cached bucket) with a one-round deferred block.  The arm
# runs in the refresh-heavy regime refresh_every=1 implies: a smaller
# per-round batch (the step's touched set stays far below C) and a
# replica holding half the vocab, so the full re-gather is a real
# fraction of the round instead of rounding error under the step
PIPE_ROUNDS = 6
PIPE_B = 4               # pipeline-arm batch: T = PIPE_B * K tokens
PIPE_C = V // 2          # pipeline-arm replica capacity
PIPELINE_MIN_SPEEDUP = 1.15


def _rows(summary) -> List[str]:
    from .common import emit
    rows: List[str] = []
    for e in summary["entries"]:
        tag = f"zipf{e['zipf']}"
        emit(rows, "mesh", "managed", tag, "lookup_us", e["managed_us"])
        emit(rows, "mesh", "plain", tag, "lookup_us", e["plain_us"])
        emit(rows, "mesh", "managed", tag, "speedup_x", e["speedup_x"])
        emit(rows, "mesh", "managed", tag, "collective_rows",
             e["buffer_rows"])
        emit(rows, "mesh", "plain", tag, "collective_rows",
             e["dense_rows"])
        emit(rows, "mesh", "managed", tag, "train_fwd_bwd_us",
             e["train_fwd_bwd_us"])
    for e in summary.get("fused", {}).get("entries", []):
        tag = f"zipf{e['zipf']}"
        emit(rows, "mesh", "fused_step", tag, "legacy_us",
             e["legacy_step_us"])
        emit(rows, "mesh", "fused_step", tag, "fused_us",
             e["fused_step_us"])
        emit(rows, "mesh", "fused_step", tag, "speedup_x", e["speedup"])
    pl = summary.get("pipeline")
    if pl:
        emit(rows, "mesh", "pipeline", "zipf1.0", "speedup_x",
             pl["speedup"])
    emit(rows, "mesh", "managed", "ALL", "managed_faster_at_zipf_ge_1",
         int(summary["managed_faster_at_zipf_ge_1"]))
    return rows


def _reexec(quick: bool, trace_path=None, metrics_path=None) -> List[str]:
    """Re-launch this module under a forced multi-device host platform
    (XLA flags are read once at jax init, so the parent process cannot
    grow devices in place).  The marker env var bounds this to ONE
    attempt: on hosts where the flag cannot raise the device count (e.g.
    a single-GPU default backend) the child fails loudly instead of
    forking an endless re-exec chain.  Observability paths ride along as
    absolute paths — the child runs with cwd at the repo root, which may
    differ from the caller's."""
    if os.environ.get("_MESH_BENCH_REEXEC"):
        raise RuntimeError(
            f"still fewer than {N_DEV} devices after forcing "
            f"--xla_force_host_platform_device_count={N_DEV}; this host's "
            "default jax backend does not honor the flag — run on CPU or "
            f"a host with >= {N_DEV} devices")
    env = dict(os.environ, _MESH_BENCH_REEXEC="1")
    flags = env.get("XLA_FLAGS", "")
    env["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={N_DEV}").strip()
    cmd = [sys.executable, "-m", "benchmarks.mesh_bench"]
    if quick:
        cmd.append("--quick")
    if trace_path:
        cmd += ["--trace", os.path.abspath(trace_path)]
    if metrics_path:
        cmd += ["--metrics-out", os.path.abspath(metrics_path)]
    subprocess.run(cmd, check=True, env=env,
                   cwd=os.path.join(os.path.dirname(
                       os.path.abspath(__file__)), ".."))
    with open(_OUT) as f:
        return _rows(json.load(f))


def _mesh_size() -> int:
    """Devices for the bench mesh, or 0 when this process must re-exec
    with ``N_DEV`` virtual CPU devices.  Only the CPU backend can be given
    virtual devices and shared with a child process; an accelerator host
    runs on the devices it has, in this process.  ``JAX_PLATFORMS=cpu``
    settles the platform before JAX initializes; otherwise the backend
    JAX picks decides."""
    cpu = os.environ.get("JAX_PLATFORMS", "").split(",")[0] == "cpu"
    import jax
    devices = jax.devices()
    if cpu or devices[0].platform == "cpu":
        return N_DEV if len(devices) >= N_DEV else 0
    return len(devices)


def _bucket(n, floor=64):
    b = floor
    while b < n:
        b *= 2
    return b


def _make_step_pair(backend, cache_ids, cache_rows, tokens, M, lr=0.1):
    """Paired mesh train-step replicas over identical inputs.  Both share
    the single-sort index stage, the jnp row math and the AdaGrad update
    rule; they differ exactly in the collective layout ISSUE 6 changed:

      legacy : PR-4 data movement — replicated psum of the (M+1, D) miss
               buffer forward, dense (V, D) partial + tiled psum_scatter
               backward, dense optimizer sweep over the sharded table;
      fused  : destination-compacted routing — per-owner all-gather of
               the miss rows forward, all_to_all routed (id, grad-row)
               pairs applied on-shard, donated table/accum, no dense
               (V, D) buffer anywhere.
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref
    from repro.kernels.pm_forward import step_residual

    T = tokens.size
    tok = tokens.reshape(-1).astype(jnp.int32)
    Dm = cache_rows.shape[1]

    def _combine(buf_rows, pc):
        buffer = jnp.concatenate(
            [buf_rows, jnp.zeros((1, Dm), buf_rows.dtype)])
        return ref.pm_combine_ref(pc.hit, pc.cache_slot, pc.buf_slot,
                                  cache_rows, buffer)

    def _row_grads(res, buf_rows):
        out = _combine(buf_rows, res.probe)
        gt = 2.0 * out                    # d sum(out^2) / d out
        seg_ids, seg_g = ops.segment_rows(tok, gt, n_slots=T, pad_id=V,
                                          residual=res.sort)
        return seg_ids, seg_g.astype(jnp.float32)

    def legacy_step(table, accum):
        res = step_residual(cache_ids, tok, M)
        buf_rows = backend.gather_rows(table, res.probe.buf_ids)
        seg_ids, seg_g = _row_grads(res, buf_rows)
        g = backend.scatter_row_grads_psum(seg_ids, seg_g, V,
                                           segmented=True)
        new_accum = accum + g * g         # dense sweep over (V/n, D)
        new_table = table - lr * g / (jnp.sqrt(new_accum) + 1e-8)
        return new_table, new_accum

    def fused_step(table, accum):
        res = step_residual(cache_ids, tok, M)
        buf_rows = backend.gather_rows_routed(table, res.probe.buf_ids,
                                              res.probe.n_miss)
        seg_ids, seg_g = _row_grads(res, buf_rows)
        return backend.update_rows(table, accum, seg_ids, seg_g, lr=lr)

    return (jax.jit(legacy_step),
            jax.jit(fused_step, donate_argnums=(0, 1)))


def _paired_step_medians(legacy, fused, table, accum, iters: int):
    """Alternate the two steps call-for-call and report each side's
    median latency (us).  The fused step donates its buffers, so every
    call receives fresh sharded copies prepared — and blocked on —
    outside the timed region."""
    import jax
    import jax.numpy as jnp

    def fused_inputs():
        pair = (jnp.copy(table), jnp.copy(accum))
        jax.block_until_ready(pair)
        return pair

    jax.block_until_ready(legacy(table, accum))        # compile
    jax.block_until_ready(fused(*fused_inputs()))
    tl, tf = [], []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(legacy(table, accum))
        tl.append(time.perf_counter() - t0)
        tc, ac = fused_inputs()
        t0 = time.perf_counter()
        jax.block_until_ready(fused(tc, ac))
        tf.append(time.perf_counter() - t0)
    return float(np.median(tl) * 1e6), float(np.median(tf) * 1e6)


def _fused_arm(quick: bool, n_dev: int, tracer=None, bus=None):
    """The ISSUE 6 acceptance measurement: routed fused step vs the PR-4
    replica, per Zipf skew, on the ``n_dev``-device mesh."""
    import jax.numpy as jnp

    from repro.data.pipeline import SyntheticCorpus
    from repro.launch.mesh import make_model_mesh
    from repro.obs import make_tracer
    from repro.pm.collectives import MeshBackend
    from repro.pm.embedding import make_state, probe_host

    tr = make_tracer(False, tracer=tracer)
    backend = MeshBackend(make_model_mesh(n_dev))
    rng = np.random.default_rng(0)
    table = backend.place_table(
        jnp.asarray(rng.normal(size=(V, D)), jnp.float32))
    accum = backend.place_table(jnp.full((V, D), 0.1, jnp.float32))
    skews = SKEWS_QUICK if quick else SKEWS_FULL
    iters = max(3, FUSED_ITERS // 2) if quick else FUSED_ITERS
    entries = []
    for zipf_a in skews:
        corpus = SyntheticCorpus(V, zipf_a=zipf_a, seed=3)
        tokens = corpus.tokens((B, K))
        cache_ids = np.sort(corpus.perm[:C]).astype(np.int32)
        probe = probe_host(cache_ids, tokens.reshape(-1), B * K)
        M = _bucket(max(1, probe.n_miss))
        st = make_state(table, jnp.asarray(cache_ids), backend)
        legacy, fused = _make_step_pair(backend, jnp.asarray(cache_ids),
                                        st.cache_rows,
                                        jnp.asarray(tokens), M)
        with tr.span("mesh.fused_skew", a=int(zipf_a * 10), b=M):
            lus, fus = _paired_step_medians(legacy, fused, table, accum,
                                            iters)
        if bus is not None:
            bus.set("mesh.fused_legacy_us", round(lus, 1), zipf=zipf_a)
            bus.set("mesh.fused_us", round(fus, 1), zipf=zipf_a)
            bus.set("mesh.fused_speedup", round(lus / fus, 3),
                    zipf=zipf_a)
        entries.append(dict(zipf=zipf_a, M=M,
                            legacy_step_us=round(lus, 1),
                            fused_step_us=round(fus, 1),
                            speedup=round(lus / fus, 3)))
        print(f"mesh,fused_step,zipf{zipf_a},us_legacy,{lus:.1f}")
        print(f"mesh,fused_step,zipf{zipf_a},us_fused,{fus:.1f}")
        print(f"mesh,fused_step,zipf{zipf_a},speedup,{lus / fus:.2f}")
    return entries


def _pipeline_arm(quick: bool, n_dev: int) -> dict:
    """§15 pipeline arm (DESIGN.md §15): the fused routed step under
    refresh-every-round replica sync, synchronous (full C-row replicated
    psum re-gather + per-round block) vs pipelined (routed delta
    re-gather of the touched ∩ cached bucket, block deferred one round)
    — paired via `benchmarks.common.paired_pooled_ratio`.  Both arms run
    the identical fused step; the delta is exact for the same reason the
    train loop's gate demands (sparse AdaGrad touches only the batch's
    rows), so the speedup is refresh traffic eliminated from the mesh."""
    import jax
    import jax.numpy as jnp

    from repro.data.pipeline import SyntheticCorpus
    from repro.launch.mesh import make_model_mesh
    from repro.pm.collectives import MeshBackend
    from repro.pm.embedding import make_state, probe_host

    from .common import paired_pooled_ratio

    backend = MeshBackend(make_model_mesh(n_dev))
    rng = np.random.default_rng(0)
    table0 = np.asarray(rng.normal(size=(V, D)), np.float32)
    accum0 = np.full((V, D), 0.1, np.float32)
    corpus = SyntheticCorpus(V, zipf_a=1.0, seed=3)
    tokens = corpus.tokens((PIPE_B, K))
    cache_np = np.sort(corpus.perm[:PIPE_C]).astype(np.int32)
    cache_ids = jnp.asarray(cache_np)
    probe = probe_host(cache_np, tokens.reshape(-1), PIPE_B * K)
    M = _bucket(max(1, probe.n_miss))
    st = make_state(backend.place_table(jnp.asarray(table0)), cache_ids,
                    backend)
    _, fused = _make_step_pair(backend, cache_ids, st.cache_rows,
                               jnp.asarray(tokens), M)
    refresh_full = jax.jit(lambda t: backend.gather_rows(t, cache_ids))
    refresh_delta = jax.jit(backend.refresh_rows_delta,
                            donate_argnums=(1,))
    # the delta bucket: the step's touched rows that live in the replica
    # (the train loop gets this set free from the loader's signal)
    touched = np.intersect1d(np.unique(tokens).astype(np.int64),
                             cache_np.astype(np.int64))
    n = _bucket(max(1, int(touched.size)))
    ids_p = np.full(n, V, np.int32)
    ids_p[:touched.size] = touched
    slots_p = np.full(n, PIPE_C, np.int32)
    slots_p[:touched.size] = np.searchsorted(cache_np, touched)
    ids_d, slots_d = jnp.asarray(ids_p), jnp.asarray(slots_p)

    def _fresh():
        t = backend.place_table(jnp.asarray(table0))
        a = backend.place_table(jnp.asarray(accum0))
        cr = refresh_full(t)
        jax.block_until_ready((t, a, cr))
        return t, a, cr

    def run_sync():
        table, accum, cache_rows = _fresh()
        out = []
        for _ in range(PIPE_ROUNDS):
            t0 = time.perf_counter()
            table, accum = fused(table, accum)
            cache_rows = refresh_full(table)
            jax.block_until_ready((table, cache_rows))   # per-round
            out.append((time.perf_counter() - t0) * 1e3)
        return out

    def run_pipe():
        table, accum, cache_rows = _fresh()
        pending = []
        out = []
        for _ in range(PIPE_ROUNDS):
            t0 = time.perf_counter()
            # deferred block from the previous round, drained BEFORE
            # this round's donating calls consume the arrays it holds
            if pending:
                jax.block_until_ready(pending.pop(0))
            table, accum = fused(table, accum)
            cache_rows = refresh_delta(table, cache_rows, ids_d, slots_d)
            pending.append((table, cache_rows))
            out.append((time.perf_counter() - t0) * 1e3)
        jax.block_until_ready(pending)
        return out

    run_sync(), run_pipe()                               # compile
    r = paired_pooled_ratio(run_sync, run_pipe,
                            reps=3 if quick else 4)
    speedup = 1.0 / r["ratio"]
    print(f"mesh,pipeline,zipf1.0,speedup,{speedup:.3f}")
    return dict(
        note=("Fused routed step + replica refresh every round: "
              "synchronous full C-row psum re-gather vs routed delta "
              "re-gather with a one-round deferred block; paired "
              "pooled medians (DESIGN.md §15)."),
        zipf=1.0, C=PIPE_C, tokens_per_round=PIPE_B * K,
        delta_bucket=n, rounds=PIPE_ROUNDS,
        sync_round_ms=round(r["median_base"], 3),
        pipelined_round_ms=round(r["median_test"], 3),
        speedup=round(speedup, 3), aa_drift=round(r["drift"], 4),
        min_speedup_required=PIPELINE_MIN_SPEEDUP)


def _geomean(vals):
    return float(np.exp(np.mean(np.log(list(vals)))))


def _run_local(quick: bool, n_dev: int, trace_path=None,
               metrics_path=None):
    import jax
    import jax.numpy as jnp

    from repro.data.pipeline import SyntheticCorpus
    from repro.launch.mesh import make_model_mesh
    from repro.obs import JsonlSink, Telemetry, make_tracer
    from repro.pm.collectives import MeshBackend
    from repro.pm.embedding import (make_state, plain_serve_lookup,
                                    planned_serve_lookup, pm_lookup,
                                    probe_host)

    from .common import time_fn

    tracer = make_tracer(bool(trace_path))
    bus = Telemetry() if metrics_path else None
    t_start = time.time()
    backend = MeshBackend(make_model_mesh(n_dev))
    rng = np.random.default_rng(0)
    table = backend.place_table(
        jnp.asarray(rng.normal(size=(V, D)), jnp.float32))

    managed_fn = jax.jit(lambda t, cr, bi, h, cs, bs: planned_serve_lookup(
        t, cr, bi, h, cs, bs, backend=backend))
    plain_fn = jax.jit(lambda t, tok: plain_serve_lookup(
        t, tok, backend=backend))

    skews = list(SKEWS_QUICK if quick else SKEWS_FULL)
    iters = ITERS // 2 if quick else ITERS
    entries = []
    for zipf_a in skews:
        corpus = SyntheticCorpus(V, zipf_a=zipf_a, seed=3)
        tokens = corpus.tokens((B, K))
        # the plan: cache the Zipf head (rank < C through the corpus
        # permutation), size the buffer by the observed unique miss count
        # — what `IntentPlanner` would derive from the signaled window
        cache_ids = np.sort(corpus.perm[:C]).astype(np.int32)
        probe = probe_host(cache_ids, tokens.reshape(-1), B * K)
        M = _bucket(max(1, probe.n_miss))
        probe = probe_host(cache_ids, tokens.reshape(-1), M)
        assert not probe.overflow.any()
        st = make_state(table, jnp.asarray(cache_ids), backend)
        idx = [jnp.asarray(a) for a in
               (probe.buf_ids, probe.hit.astype(np.int32),
                probe.cache_slot, probe.buf_slot)]
        tok_dev = jnp.asarray(tokens)
        with tracer.span("mesh.lookup_skew", a=int(zipf_a * 10), b=M):
            managed_us = time_fn(
                lambda: managed_fn(table, st.cache_rows, *idx),
                iters=iters, block=jax.block_until_ready)
            plain_us = time_fn(lambda: plain_fn(table, tok_dev),
                               iters=iters, block=jax.block_until_ready)
        if bus is not None:
            bus.set("mesh.managed_us", round(managed_us, 1), zipf=zipf_a)
            bus.set("mesh.plain_us", round(plain_us, 1), zipf=zipf_a)
            bus.set("mesh.speedup",
                    round(plain_us / max(managed_us, 1e-9), 2),
                    zipf=zipf_a)

        # training closure: fwd+bwd through the mesh VJP (psum forward,
        # psum_scatter backward) vs the dense gather/scatter
        grad_m = jax.jit(jax.grad(lambda t: jnp.sum(pm_lookup(
            t, st.cache_ids, st.cache_rows, tok_dev, M, True, False,
            backend) ** 2)))
        grad_p = jax.jit(jax.grad(lambda t: jnp.sum(
            jnp.take(t, tok_dev.reshape(-1), axis=0) ** 2)))
        train_m_us = time_fn(lambda: grad_m(table), iters=max(3, iters // 4),
                             block=jax.block_until_ready)
        train_p_us = time_fn(lambda: grad_p(table), iters=max(3, iters // 4),
                             block=jax.block_until_ready)

        entries.append({
            "zipf": zipf_a,
            "miss_capacity": M,
            "unique_misses": int(probe.n_miss),
            "miss_rate": round(float(1.0 - probe.hit.mean()), 4),
            "managed_us": round(managed_us, 1),
            "plain_us": round(plain_us, 1),
            "speedup_x": round(plain_us / max(managed_us, 1e-9), 2),
            "buffer_rows": M + 1,        # what the managed psum moves
            "dense_rows": B * K,         # what the plain psum moves
            "train_fwd_bwd_us": round(train_m_us, 1),
            "train_fwd_bwd_plain_us": round(train_p_us, 1),
        })

    fused_entries = _fused_arm(quick, n_dev, tracer=tracer, bus=bus)
    pipeline = _pipeline_arm(quick, n_dev)
    summary = {
        "config": {"vocab": V, "dim": D, "tokens_per_batch": B * K,
                   "cache_capacity": C, "devices": n_dev,
                   "iters": iters, "quick": quick},
        "entries": entries,
        "managed_faster_at_zipf_ge_1": all(
            e["speedup_x"] > 1.0 for e in entries if e["zipf"] >= 1.0),
        "fused": {
            "note": ("Routed fused managed step (all_to_all miss routing "
                     "+ on-shard sparse AdaGrad, donated buffers) vs a "
                     "PR-4 replica (replicated psum gather, dense (V, D) "
                     "partial + psum_scatter, dense optimizer sweep); "
                     "paired medians on the jnp data path."),
            "entries": fused_entries,
            "headline": {"speedup_geomean": round(_geomean(
                [e["speedup"] for e in fused_entries]), 3)},
        },
        "pipeline": pipeline,
        "wall_clock_s": round(time.time() - t_start, 2),
    }
    with open(_OUT, "w") as f:
        json.dump(summary, f, indent=1)
    print(f"wrote {os.path.normpath(_OUT)}")
    if trace_path:
        tracer.dump(trace_path)
        print(f"wrote {trace_path} ({tracer.count} spans)")
    if metrics_path:
        with JsonlSink(metrics_path) as sink:
            sink.write_bus(bus, label="mesh_bench")
        print(f"wrote {metrics_path}")
    return summary


def run(quick: bool = False, trace_path=None,
        metrics_path=None) -> List[str]:
    n_dev = _mesh_size()
    if not n_dev:
        return _reexec(quick, trace_path, metrics_path)
    return _rows(_run_local(quick, n_dev, trace_path, metrics_path))


def check_baseline(path: str, pipeline: bool = False) -> int:
    """CI regression guard: re-measure the quick fused-arm skews (or,
    with ``pipeline``, the §15 pipelined-vs-synchronous refresh rounds)
    and compare against the committed baseline, normalized through the
    paired in-process counterpart (machine-independent).  Returns a
    process exit code."""
    n_dev = _mesh_size()
    if not n_dev:
        # same one-attempt re-exec contract as `run` (see _reexec), but
        # propagating the guard's exit code instead of raising
        if os.environ.get("_MESH_BENCH_REEXEC"):
            print(f"still fewer than {N_DEV} devices after forcing the "
                  "host platform device count")
            return 1
        env = dict(os.environ, _MESH_BENCH_REEXEC="1")
        flags = env.get("XLA_FLAGS", "")
        env["XLA_FLAGS"] = (f"{flags} --xla_force_host_platform_device_"
                            f"count={N_DEV}").strip()
        return subprocess.run(
            [sys.executable, "-m", "benchmarks.mesh_bench",
             "--check-baseline", os.path.abspath(path)]
            + (["--pipeline"] if pipeline else []),
            env=env, cwd=os.path.join(os.path.dirname(
                os.path.abspath(__file__)), "..")).returncode

    with open(path) as f:
        base = json.load(f)
    if pipeline:
        committed = base.get("pipeline", {}).get("speedup")
        if not committed:
            print(f"no pipeline section baseline in {path}")
            return 1
        meas = _pipeline_arm(True, n_dev)["speedup"]
        print(f"pipeline arm: speedup now x{meas:.3f} vs committed "
              f"x{committed:.3f} (tolerance x{REGRESSION_TOL})")
        if committed / meas > REGRESSION_TOL:
            print("possible regression — re-measuring to filter noise")
            meas = max(meas, _pipeline_arm(True, n_dev)["speedup"])
            print(f"best-of-two: x{meas:.3f}")
        if committed / meas > REGRESSION_TOL:
            print(f"pipeline speedup regressed >15% vs {path}")
            return 1
        print("pipeline speedup within 15% of the committed baseline")
        return 0
    base_entries = {e["zipf"]: e
                    for e in base.get("fused", {}).get("entries", [])}
    if not base_entries:
        print(f"no fused entries baseline in {path}")
        return 1

    def measure_ratios():
        """Per-skew fused median in units of its paired legacy median,
        relative to the committed baseline (>1 = slower than
        committed)."""
        ratios = {}
        for e in _fused_arm(True, n_dev):
            if e["zipf"] not in base_entries:
                continue
            b = base_entries[e["zipf"]]
            now = e["fused_step_us"] / e["legacy_step_us"]
            then = b["fused_step_us"] / b["legacy_step_us"]
            ratios[e["zipf"]] = now / then
            print(f"zipf{e['zipf']}: fused/legacy now {now:.3f} vs "
                  f"baseline {then:.3f} (x{now / then:.2f})")
        return ratios

    ratios = measure_ratios()
    if not ratios:
        print("no overlapping zipf entries with the baseline")
        return 1
    geo = _geomean(ratios.values())
    print(f"normalized fused-step median vs baseline: x{geo:.3f} "
          f"(geomean over {len(ratios)} skews, tolerance "
          f"x{REGRESSION_TOL})")
    if geo > REGRESSION_TOL:
        # one-sided scheduler noise on a shared CI host doesn't
        # reproduce; a genuine routed-path regression does
        print("possible regression — re-measuring to filter host noise")
        second = measure_ratios()
        best = {k: min(v, second.get(k, v)) for k, v in ratios.items()}
        geo = _geomean(best.values())
        print(f"best-of-two normalized median: x{geo:.3f}")
    if geo > REGRESSION_TOL:
        print(f"fused mesh step regressed >15% vs {path}")
        return 1
    print("fused mesh step within 15% of the committed baseline")
    return 0


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized smoke (2 skews, half the iters)")
    ap.add_argument("--check-baseline", metavar="JSON", default=None,
                    help="regression guard: compare the fused arm "
                    "against a committed BENCH_mesh.json instead of "
                    "writing results")
    ap.add_argument("--pipeline", action="store_true",
                    help="with --check-baseline: guard the §15 pipeline "
                    "arm (pipelined vs synchronous refresh, paired)")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="write per-skew spans as Chrome trace-event "
                    "JSON to PATH")
    ap.add_argument("--metrics-out", metavar="PATH", default=None,
                    help="write per-skew gauges as schema-versioned "
                    "JSONL to PATH")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.check_baseline:
        raise SystemExit(check_baseline(args.check_baseline,
                                        pipeline=args.pipeline))
    run(quick=args.quick, trace_path=args.trace,
        metrics_path=args.metrics_out)
