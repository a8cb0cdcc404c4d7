#!/usr/bin/env python3
"""Drive the intent-managed train step and the serving runtime once on TPU.

Run from the repository root, in a process of its own (the chips belong
to the first process that touches JAX):

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the vocab-sharded mesh paths, 4 chips

One chip runs three phases, each through the entry points a user calls:

  kernels  the four Pallas row kernels (`kernels.ops`) at the smollm-135m
           table width, 49152 x 576 f32, each compiled natively (the
           program holds a ``tpu_custom_call``) and checked against its
           `kernels.ref` oracle;
  train    smollm-135m at full width with random weights from a seed,
           through `train.loop.train_loop` with the managed lookup on,
           ``kernel=True``, batch 8 x seq 1024 for 20 steps: losses finite
           and falling, the compiled train step holds the kernels, and
           every loss matches the same run with ``kernel=False``;
  serve    `serve.runtime.ServingRuntime` over a random 49152 x 576 f32
           table with the Pallas data path, fed by a `DriftingZipfStream`
           for 32 rounds: every served row equals ``table[keys]`` and no
           request is served zeros.

``--chips 4`` runs only what exists across chips, each beside its
single-chip reference: mesh training (``collective="mesh"``,
``model_shards=4``) against the emulated run on one chip, and mesh serving
against the emulated lookup; the table must sit in four V/4-row shards on
four distinct devices.

Every failed check raises, so the process exits non-zero and prints no
result.  Without a TPU it exits non-zero before any phase.  The times it
prints are smoke timings of one run, not benchmark numbers.  The last
line of standard output is one JSON object naming the device:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
V, D = 49152, 576          # smollm-135m vocabulary x width
BATCH, SEQ = 8, 1024       # T = 8192 tokens per training step
TRAIN_STEPS = 20
CACHE_ROWS = 4096          # replica-cache capacity, pinned for both runs
SERVE_ROUNDS = 32
SERVE_BATCH, SERVE_KEYS = 64, 64
# Two runs that differ only in how rows move compute the same first loss
# (same weights, same batch) up to f32 reduction order.  After that they
# drift apart: gradient sums are ordered differently (duplicate tokens;
# on the mesh, the vocab-partitioned head), and AdaGrad's first update is
# lr * sign(g) per entry, so a rounding-size gradient near zero becomes a
# parameter difference of order lr.  On a v5e the drift reached 7.1e-4
# (kernel vs jnp) and 1.3e-2 (mesh vs one chip) within 20 steps, while
# the loss itself falls by ~40%.
FIRST_LOSS_RTOL = 1e-5
KERNEL_LOSS_RTOL = 5e-3
MESH_LOSS_RTOL = 2e-2
MESH_SHARDS = 4


class CompileClock:
    """Backend-compile and persistent-cache-read seconds JAX reports."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name in self.EVENTS:
            self.seconds += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def lap(self):
        """(seconds, cache hits) since the previous lap."""
        out = (self.seconds, self.cache_hits)
        self.seconds, self.cache_hits = 0.0, 0
        return out


def say(*parts):
    print("chip_smoke:", *parts, flush=True)


def check(cond, what):
    if not cond:
        raise AssertionError(what)


# ------------------------------------------------------------------ kernels

def kernel_phase(seed: int, clock: CompileClock):
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    T = BATCH * SEQ
    C, M = V // 8, T
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    table = jax.random.normal(k[0], (V, D), jnp.float32)
    accum = jax.random.uniform(k[1], (V, D), jnp.float32, 0.01, 1.0)
    uniq = jax.random.permutation(k[2], V)[:T].astype(jnp.int32)
    ids = jax.random.randint(k[3], (T,), 0, V, jnp.int32)
    rows = jax.random.normal(k[4], (T, D), jnp.float32)
    hit = jax.random.bernoulli(k[5], 0.6, (T,))
    cslot = jax.random.randint(k[6], (T,), 0, C, jnp.int32)
    bslot = jax.random.randint(k[7], (T,), 0, M + 1, jnp.int32)
    cache_rows, buf_rows = table[:C], table[C:C + M + 1]

    cases = [
        ("embed_gather", ops.embed_gather, ref.embed_gather_ref,
         (table, ids), 0.0),
        ("scatter_rows", ops.scatter_rows, ref.scatter_rows_ref,
         (jnp.zeros((V + 1, D), jnp.float32), uniq, rows), 0.0),
        ("adagrad_rows",
         lambda t, a, i, g: ops.adagrad_row_update(t, a, i, g, lr=0.05),
         lambda t, a, i, g: ref.adagrad_row_update_ref(t, a, i, g, lr=0.05),
         (table, accum, uniq, rows), 1e-5),
        ("pm_combine", ops.pm_combine, ref.pm_combine_ref,
         (hit, cslot, bslot, cache_rows, buf_rows), 0.0),
    ]
    for name, kernel, oracle, args, rtol in cases:
        compiled = jax.jit(kernel).lower(*args).compile()
        check("tpu_custom_call" in compiled.as_text(),
              f"{name}: no tpu_custom_call in the compiled program")
        got = jax.tree_util.tree_leaves(compiled(*args))
        want = jax.tree_util.tree_leaves(jax.jit(oracle)(*args))
        for g, w in zip(got, want):
            g, w = np.asarray(g), np.asarray(w)
            if rtol:
                np.testing.assert_allclose(g, w, rtol=rtol, atol=1e-6)
            else:
                np.testing.assert_array_equal(g, w)
        say(f"kernel {name}: native, matches kernels/ref.py"
            + (f" (rtol {rtol})" if rtol else " exactly"))
    comp_s, hits = clock.lap()
    say(f"kernels compile {comp_s:.1f}s ({hits} cache hits)")


# -------------------------------------------------------------------- train

def train_run(kernel: bool, seed: int, collective: str = "emulated",
              model_shards: int = 0, ir_dir: str | None = None):
    """One `train_loop` run of smollm-135m at full width.  Returns the
    per-step losses and step wall times (ms; the first step of each miss
    bucket includes its compile)."""
    import jax

    from repro.configs.registry import get_config
    from repro.obs.telemetry import Telemetry
    from repro.train.loop import LoopConfig, train_loop

    cfg = get_config("smollm-135m", smoke=False)
    lc = LoopConfig(steps=TRAIN_STEPS, batch=BATCH, seq=SEQ, pm=True,
                    kernel=kernel, collective=collective,
                    model_shards=model_shards, cache_capacity=CACHE_ROWS,
                    refresh_every=1, pipeline_depth=0, log_every=0,
                    seed=seed)
    bus = Telemetry()
    if ir_dir:
        jax.config.update("jax_dump_ir_to", ir_dir)
    try:
        res = train_loop(cfg, lc, telemetry=bus)
    finally:
        if ir_dir:
            jax.config.update("jax_dump_ir_to", "")
    losses = np.asarray(res.losses, np.float64)
    check(losses.shape == (TRAIN_STEPS,), f"{losses.shape[0]} losses")
    check(np.isfinite(losses).all(), f"non-finite loss: {losses}")
    step_ms = np.asarray(bus.latency("train.step_ms").values())
    return losses, step_ms, res


def compare_losses(got, want, rtol: float) -> str:
    """Check two runs' per-step losses: the first at `FIRST_LOSS_RTOL`,
    every step at ``rtol``."""
    np.testing.assert_allclose(got[0], want[0], rtol=FIRST_LOSS_RTOL)
    np.testing.assert_allclose(got, want, rtol=rtol)
    rel = np.abs(got - want) / np.abs(want)
    return (f"first-step rel diff {rel[0]:.2e} (rtol {FIRST_LOSS_RTOL}), "
            f"max {rel.max():.2e} (rtol {rtol})")


def _step_ir_has_kernel(ir_dir: str) -> bool:
    files = glob.glob(os.path.join(ir_dir, "*jit_train_step*.mlir"))
    check(files, "no train step IR was dumped")
    return all("tpu_custom_call" in open(f).read() for f in files)


def train_phase(seed: int, clock: CompileClock):
    with tempfile.TemporaryDirectory() as ir_dir:
        losses, step_ms, res = train_run(True, seed, ir_dir=ir_dir)
        check(_step_ir_has_kernel(ir_dir),
              "a compiled train step holds no tpu_custom_call")
    comp_s, hits = clock.lap()
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    say(f"train kernel=True: losses {losses[0]:.4f} -> {losses[-1]:.4f}, "
        f"{res.recompiles} step programs, {res.overflows} overflow steps, "
        f"compile {comp_s:.1f}s ({hits} cache hits)")
    say(f"train kernel=True smoke timing: first step {step_ms[0]:.1f} ms, "
        f"median step {np.median(step_ms[1:]):.1f} ms")
    ref_losses, ref_ms, _ = train_run(False, seed)
    comp_ref, hits_ref = clock.lap()
    match = compare_losses(losses, ref_losses, KERNEL_LOSS_RTOL)
    say(f"train kernel=False: matches kernel=True per step ({match}), "
        f"compile {comp_ref:.1f}s ({hits_ref} cache hits), "
        f"median step {np.median(ref_ms[1:]):.1f} ms (smoke timing)")


# -------------------------------------------------------------------- serve

def serve_run(table, seed: int, collective: str = "emulated",
              model_shards: int = 0):
    """`SERVE_ROUNDS` rounds of the serving runtime; checks every served
    row against ``table[keys]`` and returns (runtime, result)."""
    from repro.serve.requests import DriftingZipfStream, ReplayStream
    from repro.serve.runtime import ServeConfig, ServingRuntime

    stream = DriftingZipfStream(V, keys_per_request=SERVE_KEYS,
                                arrival_rate=SERVE_BATCH, seed=seed)
    replay = ReplayStream.record(stream, 2 * SERVE_ROUNDS)
    keys = {r.rid: r.keys for rnd in replay.per_round for r in rnd}
    cfg = ServeConfig(vocab=V, kernel=True, n_shards=1,
                      batch_requests=SERVE_BATCH,
                      keys_per_request=SERVE_KEYS, collective=collective,
                      model_shards=model_shards, summary=False, seed=seed)
    rt = ServingRuntime(table, cfg)
    res = rt.run(replay, SERVE_ROUNDS, collect_outputs=True)
    host = np.asarray(table)
    check(res.served > 0 and len(res.outputs) == res.served,
          f"served {res.served}, outputs {len(res.outputs)}")
    check(res.zero_served == 0, f"{res.zero_served} zero-served requests")
    for rid, rows in res.outputs.items():
        np.testing.assert_array_equal(rows, host[keys[rid]])
    return rt, res


def serve_phase(seed: int, clock: CompileClock):
    import jax
    import jax.numpy as jnp
    table = jax.random.normal(jax.random.PRNGKey(seed + 1), (V, D),
                              jnp.float32)
    _, res = serve_run(table, seed)
    comp_s, hits = clock.lap()
    say(f"serve: {res.served} requests over {res.rounds} rounds, every row "
        f"equals table[keys], {res.zero_served} zero-served, "
        f"{res.replans} replans, {res.requeues} requeues, "
        f"compile {comp_s:.1f}s ({hits} cache hits)")
    say(f"serve smoke timing: {1e3 * res.wall_s / res.rounds:.2f} ms per "
        f"round, p50 {res.p50_ms:.2f} ms, p99 {res.p99_ms:.2f} ms")


# --------------------------------------------------------------- four chips

def check_sharded(arr, n: int, what: str):
    shards = arr.addressable_shards
    devices = {s.device for s in shards}
    rows = sorted(s.data.shape[0] for s in shards)
    check(len(shards) == n and len(devices) == n,
          f"{what}: {len(shards)} shards on {len(devices)} devices")
    check(rows == [V // n] * n, f"{what}: shard rows {rows}")
    say(f"{what}: {n} shards of {V // n} rows on devices "
        f"{sorted(d.id for d in devices)}")


def mesh_phase(seed: int, clock: CompileClock):
    import jax
    import jax.numpy as jnp

    from repro.pm.collectives import make_backend

    n = MESH_SHARDS
    check(len(jax.devices()) >= n, f"--chips {n} needs {n} devices, "
          f"found {len(jax.devices())}")
    backend = make_backend("mesh", n)
    check_sharded(backend.place_table(jnp.zeros((V, D), jnp.float32)), n,
                  "train table placement")
    mesh_losses, mesh_ms, _ = train_run(True, seed, collective="mesh",
                                        model_shards=n)
    comp_mesh, _ = clock.lap()
    ref_losses, ref_ms, _ = train_run(True, seed)
    comp_ref, _ = clock.lap()
    match = compare_losses(mesh_losses, ref_losses, MESH_LOSS_RTOL)
    check(mesh_losses[-1] < mesh_losses[0],
          f"mesh loss did not fall: {mesh_losses}")
    say(f"train mesh x{n}: losses {mesh_losses[0]:.4f} -> "
        f"{mesh_losses[-1]:.4f}, match the emulated single-chip run "
        f"({match}); compile "
        f"{comp_mesh:.1f}s mesh, {comp_ref:.1f}s emulated; median step "
        f"{np.median(mesh_ms[1:]):.1f} ms mesh, "
        f"{np.median(ref_ms[1:]):.1f} ms emulated (smoke timing)")

    table = jax.random.normal(jax.random.PRNGKey(seed + 1), (V, D),
                              jnp.float32)
    rt, mesh_res = serve_run(table, seed, collective="mesh",
                             model_shards=n)
    check_sharded(rt.table, n, "serve table")
    _, ref_res = serve_run(table, seed)
    comp_s, _ = clock.lap()
    both = set(mesh_res.outputs) & set(ref_res.outputs)
    check(both, "no request was served by both runtimes")
    for rid in both:
        np.testing.assert_array_equal(mesh_res.outputs[rid],
                                      ref_res.outputs[rid])
    say(f"serve mesh x{n}: {mesh_res.served} requests, every row equals "
        f"table[keys] and the emulated lookup's ({len(both)} compared), "
        f"{mesh_res.zero_served} zero-served, compile {comp_s:.1f}s; "
        f"{1e3 * mesh_res.wall_s / mesh_res.rounds:.2f} ms per round "
        f"(smoke timing)")


# --------------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, MESH_SHARDS),
                    default=1, help="1: kernels, train and serve on one "
                    "chip; 4: only the mesh paths beside their references")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    say(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}, "
        f"compile cache {cache_dir}")
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.chips == 1:
        phases = (("kernels", kernel_phase), ("train", train_phase),
                  ("serve", serve_phase))
    else:
        phases = (("mesh", mesh_phase),)
    for name, phase in phases:
        t = time.perf_counter()
        phase(args.seed, clock)
        say(f"phase {name} done in {time.perf_counter() - t:.1f}s")
    for d in jax.devices():
        stats = d.memory_stats() or {}
        say(f"device {d.id} peak_bytes_in_use "
            f"{stats.get('peak_bytes_in_use', 'not reported')}")
    say(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
