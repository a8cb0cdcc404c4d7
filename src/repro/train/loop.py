"""Training loop with first-class intent-managed parameter management.

Per step:
  1. the loader (already ``prefetch`` steps ahead) has signaled intent for
     upcoming batches;
  2. the planner (Algorithm 1 timing) decides whether to act: emit a new
     placement plan (replica-cache contents + miss-buffer capacity);
  3. the replica cache is synchronized from the owner-sharded table (one
     grouped gather per *refresh round* — AdaPM's batched replica sync:
     on replan rounds, plus every ``refresh_every`` steps; in between,
     replicas serve reads at most one refresh round stale);
  4. the train step runs with the managed embedding path (optionally the
     Pallas-kernel-backed one, ``LoopConfig.kernel``; with
     ``LoopConfig.collective="mesh"`` the table is vocab-sharded over a
     real device mesh and the lookup/backward/refresh run through the
     shard_map collectives of `pm.collectives.MeshBackend`).

Miss-capacity buckets map to distinct compiled executables; the bucket
ladder is small (powers of two) so recompiles amortize away.

``LoopResult.overflows`` counts steps whose actual unique-miss count
exceeded the plan's capacity (forcing the lookup's dense fallback); with
exact intent this stays 0 — the planner's bound is exact.

Zero-tuning (DESIGN.md §13): ``cache_capacity`` and ``refresh_every``
accept ``"auto"`` (the default) and are then owned by the online
controller — capacity follows the planning window's cache-worthy demand
(`PlacementPlan.demand`, the intent signal) over power-of-two buckets,
resized exactly at replan boundaries (the managed lookup is exact
regardless of cache contents, so resizes can never change the loss
trajectory — they only move misses); refresh cadence is hill-climbed on
measured loss-drop per second (the convergence-rate reward).  Progress
signals (step latency, loss, plans, refreshes, overflows, resizes) are
published to the `repro.obs.telemetry` bus (``train.*`` records).
"""

from __future__ import annotations

import os
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import checkpoint
from repro.configs.base import ModelConfig
from repro.data.pipeline import IntentSignalingLoader
from repro.models.model import init_model
from repro.obs.telemetry import Telemetry
from repro.obs.trace import SpanTracer, make_tracer, watch_compiles
from repro.pm.controller import (AUTO, Knob, OnlineController,
                                 capacity_ladder, is_auto, resolve_knob)
from repro.pm.embedding import make_state
from repro.pm.planner import IntentPlanner, PlacementPlan
from repro.train.steps import make_opt_init, make_train_step


@dataclass
class LoopConfig:
    steps: int = 50
    batch: int = 8
    seq: int = 64
    lr: float = 0.01
    optimizer: str = "adagrad"
    pm: bool = True                  # intent-managed embedding on/off
    kernel: bool = False             # Pallas-backed managed hot path
    collective: str = "emulated"     # "emulated" | "mesh": the managed
    #                                  lookup's collective backend
    #                                  (pm/collectives.py); "mesh" shards
    #                                  the table over a real device mesh
    #                                  and runs the shard_map psum path
    model_shards: int = 0            # mesh size for collective="mesh"
    #                                  (0 = every local device)
    cache_capacity: Union[int, str] = AUTO  # replica-cache rows; "auto"
    #                                  (the default): steered by the
    #                                  planning window's intent demand
    #                                  over power-of-two buckets
    n_shards: int = 1
    prefetch: int = 16
    plan_every: int = 8
    refresh_every: Union[int, str] = AUTO  # replica sync cadence (steps);
    #                                  replan rounds always refresh.
    #                                  "auto": hill-climbed on measured
    #                                  loss-drop/s (starts at 1, the old
    #                                  hand-set default)
    pipeline_depth: Union[int, str] = AUTO  # prefetch pipeline (DESIGN.md
    #                                  §15): 0 = fully synchronous (the
    #                                  pre-ISSUE-9 loop, bitwise); >= 1
    #                                  defers loss blocking up to that
    #                                  many steps, runs the planner one
    #                                  replan round ahead in a background
    #                                  thread, and switches eligible
    #                                  refresh rounds to the delta
    #                                  re-gather of only the rows touched
    #                                  since the last sync.  "auto":
    #                                  starts at 1, hill-climbed
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 0
    init_from: Optional[str] = None  # checkpoint dir to restore from
    log_every: int = 10
    seed: int = 0


@dataclass
class LoopResult:
    losses: List[float] = field(default_factory=list)
    plans: int = 0
    refreshes: int = 0               # replica-cache sync rounds
    overflows: int = 0               # steps with unique misses > capacity
    recompiles: int = 0
    capacity_resizes: int = 0        # mid-run replica-cache bucket changes
    start_step: int = 0              # first step index (restored runs)
    wall_s: float = 0.0
    knobs: Dict[str, object] = field(default_factory=dict)
    #   the loop's knob values at the end of the run (auto knobs land
    #   wherever the controller drove them)


def train_loop(cfg: ModelConfig, lc: LoopConfig,
               telemetry: Optional[Telemetry] = None,
               tracer: Optional[SpanTracer] = None) -> LoopResult:
    t0 = time.time()
    bus = telemetry if telemetry is not None else Telemetry()
    # per-phase span tracing (DESIGN.md §14): default-off no-op unless
    # the caller injects an enabled tracer (launch/train.py --trace)
    tr = make_tracer(False, tracer=tracer)
    # every compile of the loop: `jit.compiles` on the bus, a
    # `jit.compile` span when traced (held until the loop returns)
    compile_watch = watch_compiles(bus, tr)
    key = jax.random.PRNGKey(lc.seed)
    params = init_model(cfg, key)
    opt_state = make_opt_init(lc.optimizer)(params)

    res = LoopResult()
    if lc.init_from:
        # accept either a step_XXXXXXX directory or a checkpoint root
        # (resolved to its newest step)
        path = lc.init_from
        if not os.path.exists(os.path.join(path, "manifest.json")):
            latest = checkpoint.latest_step(path)
            if latest is None:
                raise FileNotFoundError(
                    f"no checkpoint under {path!r} (expected a manifest or "
                    f"step_* subdirectories)")
            path = latest
        restored, res.start_step = checkpoint.load(
            path, {"params": params, "opt": opt_state})
        params, opt_state = restored["params"], restored["opt"]

    # collective backend for the managed lookup: the emulated single-
    # device reference, or the real shard_map psum path over a vocab-
    # sharded table (DESIGN.md §10) — in which case the table (and its
    # optimizer accumulator) is placed owner-sharded up front and every
    # gather/scatter/refresh below runs through explicit mesh collectives
    backend = None
    if lc.pm:
        from repro.pm.collectives import make_backend
        backend = make_backend(lc.collective, lc.model_shards)
    if backend is not None:
        params["embed"] = backend.place_table(params["embed"])
        opt_state = jax.tree_util.tree_map(
            lambda a: backend.place_table(a)
            if a.shape == params["embed"].shape else a, opt_state)

    # ---- knob resolution: "auto" fields belong to the controller
    auto = {name for name, v in (("cache_capacity", lc.cache_capacity),
                                 ("refresh_every", lc.refresh_every),
                                 ("pipeline_depth", lc.pipeline_depth))
            if is_auto(v)}
    cap_ladder = capacity_ladder(cfg.vocab_size)
    cache_capacity = int(resolve_knob(lc.cache_capacity, cap_ladder[0]))
    refresh_every = int(resolve_knob(lc.refresh_every, 1))
    pipeline_depth = int(resolve_knob(lc.pipeline_depth, 1))
    ctl: Optional[OnlineController] = None
    if lc.pm and auto:
        knobs = []
        if "cache_capacity" in auto:
            # intent-steered, not hill-climbed: the window's demand
            # computes the bucket directly (controller.steer_capacity)
            knobs.append(Knob("cache_capacity", cap_ladder,
                              index=cap_ladder.index(cache_capacity),
                              adapt=False, prefer_low=True))
        if "refresh_every" in auto:
            # 0 = replan rounds only; >0 adds a between-replan cadence
            ladder = (0, 1, 2, 4, 8)
            knobs.append(Knob("refresh_every", ladder,
                              index=ladder.index(refresh_every),
                              prefer_low=True))
        if "pipeline_depth" in auto:
            # the lookup is exact at every depth (the pipeline only moves
            # blocking and refresh traffic), so the hill-climb can probe
            # freely on the loss-drop/s reward
            ladder = (0, 1, 2, 4)
            knobs.append(Knob("pipeline_depth", ladder,
                              index=ladder.index(pipeline_depth),
                              prefer_low=True))
        ctl = OnlineController(knobs, bus, seed=lc.seed)

    # n_nodes = the training data shards signaling intent (§4.1 nodes):
    # a key wanted by >= 2 shards in the window is concurrent intent
    # the planner, controller and loop publish on ONE shared bus — the
    # caller's `telemetry` (or this run's fresh one), never a second,
    # divergent bus (mirrors ServingRuntime's explicit telemetry= arg)
    planner = IntentPlanner(cfg.vocab_size, cache_capacity,
                            n_nodes=max(1, lc.n_shards),
                            plan_every=lc.plan_every,
                            per_node_bound=backend is not None,
                            telemetry=bus) if lc.pm else None
    loader = IntentSignalingLoader(
        cfg, lc.batch, lc.seq, n_shards=max(1, lc.n_shards),
        prefetch=lc.prefetch, planner=planner, seed=lc.seed)

    step_fns: Dict[int, callable] = {}

    def step_fn(miss_capacity: int):
        if miss_capacity not in step_fns:
            # params + optimizer state are donated: the (V, D) table and
            # its AdaGrad accumulator — the step's hot buffers — are
            # updated in place instead of being copied every step (the
            # loop rebinds both from the step's outputs, so the old
            # buffers are dead the moment the call returns).  This holds
            # on the mesh path too: the NamedSharding'd table/accumulator
            # enter and leave the fused step with the same P("model",
            # None) layout, so XLA aliases the sharded buffers (pinned by
            # the re-feed guard test in tests/test_collectives.py)
            step_fns[miss_capacity] = jax.jit(
                make_train_step(
                    cfg, optimizer=lc.optimizer, lr=lc.lr,
                    pm_miss_capacity=miss_capacity, pm_kernel=lc.kernel,
                    pm_backend=backend),
                donate_argnums=(0, 1))
        return step_fns[miss_capacity]

    plan: Optional[PlacementPlan] = None
    cache_ids = None
    cache_rows = None
    # controller reward epochs: measured between replan boundaries
    epoch_t0: Optional[float] = None
    epoch_loss: Optional[float] = None

    # ---- prefetch pipeline state (DESIGN.md §15)
    # deferred loss blocking: the device queue holds up to pipeline_depth
    # dispatched-but-unread steps; draining preserves the synchronous
    # loop's exact per-step ordering of losses/telemetry/logs
    pending: deque = deque()   # (step, loss_device, step_t0)

    def drain(limit: int) -> None:
        while len(pending) > limit:
            s, loss_d, t0s = pending.popleft()
            _t = tr.now_ns() if tr.enabled else 0
            loss_f = float(loss_d)          # blocks on the device queue
            if tr.enabled:
                tr.record("prefetch.drain", _t, tr.now_ns(), a=s)
            res.losses.append(loss_f)
            bus.set("train.loss", loss_f)
            bus.observe("train.step_ms",
                        (time.perf_counter() - t0s) * 1e3)
            if lc.log_every and s % lc.log_every == 0:
                print(f"step {s:5d}  loss {loss_f:.4f}")

    # background plan-ahead: ONE worker builds the next boundary's plan
    # candidate off the already-signaled window while steps run; windows
    # are computed on the main thread (`plan_window`) and candidates only
    # become plans through `adopt`'s window-equality check
    executor = ThreadPoolExecutor(max_workers=1) \
        if planner is not None else None
    pending_plan = None        # (future, target_step, window)
    last_plan_step = -1
    # delta refresh: union of table rows the steps since the last sync
    # actually updated (the loader's signaled ids — exact for the sparse
    # and dense AdaGrad paths; see the refresh gate below)
    touched = np.zeros(0, dtype=np.int64)
    touched_known = True
    delta_refresh = None
    if lc.pm:
        from repro.pm.collectives import resolve
        delta_refresh = jax.jit(resolve(backend).refresh_rows_delta,
                                donate_argnums=(1,))
    # delta refresh is exact only when untouched rows are bitwise frozen
    # between syncs: sparse/dense AdaGrad leaves zero-grad rows unchanged
    # (acc + 0^2 == acc, p - lr*0 == p), but tied embeddings take dense
    # head gradients on every row and momentum-style optimizers decay
    # untouched rows' state — those always take the full re-gather
    delta_exact = (lc.optimizer == "adagrad"
                   and not getattr(cfg, "tie_embeddings", False))

    it = iter(loader)
    while True:
        # the loader's __next__ IS the intent-signaling phase: pulling a
        # batch signals its (and the prefetch horizon's) ids
        _t_sig = tr.now_ns() if tr.enabled else 0
        try:
            step, batch = next(it)
        except StopIteration:
            break
        if tr.enabled:
            tr.record("train.signal", _t_sig, tr.now_ns(), a=step)
        if step >= lc.steps:
            break
        step_t0 = time.perf_counter()
        if planner is not None:
            planner.observe_round(step)
            replanned = False
            if planner.should_replan(step, plan):
                _t_plan = tr.now_ns() if tr.enabled else 0
                # the controller's reward reads the epoch's losses — the
                # deferred tail must land in res.losses first, exactly as
                # the synchronous loop would have blocked step by step
                drain(0)
                # measured hill-climb decision at the boundary: reward is
                # the epoch's loss-drop per second (convergence rate)
                now = time.perf_counter()
                if ctl is not None and epoch_t0 is not None \
                        and res.losses:
                    cur = float(np.mean(res.losses[-lc.plan_every:]))
                    if epoch_loss is not None and now > epoch_t0:
                        reward = (epoch_loss - cur) / (now - epoch_t0)
                        bus.set("ctl.reward", reward)
                        for name, v in ctl.observe(reward).items():
                            if name == "refresh_every":
                                refresh_every = int(v)
                            elif name == "pipeline_depth":
                                pipeline_depth = int(v)
                    epoch_loss = cur
                elif ctl is not None and res.losses:
                    epoch_loss = float(np.mean(res.losses[-lc.plan_every:]))
                epoch_t0 = now
                # plan-ahead adoption: the background candidate becomes
                # the plan iff it covers exactly the window a synchronous
                # build would — otherwise (horizon moved under it) fall
                # back to building here, bitwise the pre-pipeline path
                cand = None
                if pending_plan is not None:
                    cand = pending_plan[0].result()
                    pending_plan = None
                plan = planner.adopt(cand, step)
                if plan is not None:
                    bus.inc("train.prefetch_plan_hits")
                else:
                    if cand is not None:
                        bus.inc("train.prefetch_plan_misses")
                    plan = planner.plan(step)
                if ctl is not None and "cache_capacity" in auto:
                    # intent-signal capacity steering: the window's demand
                    # count IS the bucket; a changed bucket re-plans over
                    # the same signals so plan and cache stay consistent
                    new_cap = ctl.steer_capacity("cache_capacity",
                                                 plan.demand)
                    if new_cap is not None:
                        cache_capacity = int(new_cap)
                        planner.set_capacity(cache_capacity)
                        res.capacity_resizes += 1
                        bus.inc("train.capacity_resizes")
                        bus.event("train.capacity_resize", step=step,
                                  capacity=cache_capacity)
                        plan = planner.plan(step)
                cache_ids = jnp.asarray(plan.cache_ids)
                res.plans += 1
                bus.inc("train.plans")
                replanned = True
                last_plan_step = step
                planner.gc(step)
                if tr.enabled:
                    tr.record("train.plan", _t_plan, tr.now_ns(), a=step)
            # replica sync round: re-gather hot rows from the live table —
            # once per refresh round (replan rounds + the refresh_every
            # cadence), NOT every step; replicas in between are at most one
            # refresh round stale (pm/embedding.py docstring bound)
            if replanned or cache_rows is None or (
                    refresh_every > 0
                    and step % refresh_every == 0):
                # delta refresh (pipeline on, same plan, exact-update
                # optimizer, touched set known): re-gather only the
                # cache rows the steps since the last sync updated and
                # scatter them into the DONATED previous cache buffer.
                # Bitwise the full re-gather — untouched rows are frozen
                # in the table between syncs (see delta_exact above)
                ids = None
                if (pipeline_depth >= 1 and not replanned
                        and cache_rows is not None and touched_known
                        and delta_exact):
                    ids = np.intersect1d(
                        touched, np.asarray(plan.cache_ids, np.int64))
                    n = max(64, 1 << (int(ids.size) - 1).bit_length()) \
                        if ids.size else 64
                    if n >= plan.cache_ids.shape[0]:
                        ids = None       # near-full delta: one gather wins
                if ids is not None:
                    C = plan.cache_ids.shape[0]
                    slots = np.searchsorted(
                        np.asarray(plan.cache_ids, np.int64), ids)
                    ids_p = np.full(n, cfg.vocab_size, np.int32)
                    ids_p[:ids.size] = ids
                    slots_p = np.full(n, C, np.int32)
                    slots_p[:ids.size] = slots
                    with tr.span("prefetch.refresh", a=step):
                        cache_rows = delta_refresh(
                            params["embed"], cache_rows,
                            jnp.asarray(ids_p), jnp.asarray(slots_p))
                    bus.inc("train.delta_refreshes")
                else:
                    with tr.span("train.refresh", a=step):
                        state = make_state(params["embed"], cache_ids,
                                           backend)
                        cache_rows = state.cache_rows
                touched = np.zeros(0, dtype=np.int64)
                touched_known = True
                res.refreshes += 1
                bus.inc("train.refreshes")
            batch = dict(batch,
                         pm_cache_ids=cache_ids.astype(jnp.int32),
                         pm_cache_rows=cache_rows)
            # exact-bound accounting: with deduped misses, unique misses
            # must fit the plan's capacity (zero dense-fallback rounds).
            # The loader's host-side signals ARE the step's unique ids —
            # no device-to-host readback on the hot path.
            uniq = planner.signaled_ids(step)
            if uniq is not None:
                n_miss = np.setdiff1d(uniq, plan.cache_ids).size
                if n_miss > plan.miss_capacity:
                    res.overflows += 1
                    bus.inc("train.overflows")
                # the step's unique ids are exactly the table rows its
                # optimizer update touches — the delta-refresh work set
                touched = np.union1d(touched, uniq.astype(np.int64))
            else:
                touched_known = False
            fn = step_fn(plan.miss_capacity)
            # plan-ahead submission: the earliest possible next boundary
            # is min(last boundary + plan_every, window end); one step
            # before it, hand the worker the window to build against.
            # A candidate whose predicted boundary slipped (the horizon
            # test deferred the replan) is discarded and resubmitted.
            if (executor is not None and pipeline_depth >= 1
                    and plan is not None):
                if pending_plan is not None and pending_plan[1] <= step:
                    pending_plan[0].result()
                    pending_plan = None
                t_pred = min(last_plan_step + lc.plan_every,
                             plan.window[1])
                if pending_plan is None and step == t_pred - 1:
                    window = planner.plan_window(t_pred)
                    fut = executor.submit(planner.plan_candidate, window)
                    pending_plan = (fut, t_pred, window)
                    if tr.enabled:
                        _t = tr.now_ns()
                        tr.record("prefetch.plan", _t, _t, a=t_pred)
        else:
            fn = step_fn(0)
        with tr.span("train.step", a=step):
            loss, params, opt_state = fn(params, opt_state, batch)
            if pipeline_depth == 0:
                # blocks: the span covers real step time (the synchronous
                # contract); at depth >= 1 the block moves to drain()
                loss = float(loss)
        pending.append((step, loss, step_t0))
        drain(pipeline_depth)
        if lc.ckpt_dir and lc.ckpt_every and step and \
                step % lc.ckpt_every == 0:
            checkpoint.save(f"{lc.ckpt_dir}/step_{step:07d}",
                            {"params": params, "opt": opt_state}, step)

    drain(0)
    if pending_plan is not None:
        pending_plan[0].result()
        pending_plan = None
    if executor is not None:
        executor.shutdown(wait=True)

    del compile_watch
    res.recompiles = len(step_fns)
    res.wall_s = time.time() - t0
    res.knobs = {"cache_capacity": cache_capacity,
                 "refresh_every": refresh_every,
                 "pipeline_depth": pipeline_depth,
                 "plan_every": lc.plan_every}
    return res
