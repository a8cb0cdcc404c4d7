"""Tests for the intent-lead-time prefetch pipeline (DESIGN.md §15):
plan-ahead candidates, generation-keyed probe views, delta replica
refresh, and the N-deep serving pipeline — every one an *exactness*
claim: the pipelined path must be byte-identical to the synchronous
path it overlaps, because prefetch is a wall-clock transform, never a
semantics change.

Mesh cases follow tests/test_collectives.py: string-form skipifs so
collection never freezes the jax device count."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.obs.telemetry import Telemetry
from repro.pm.collectives import EmulatedBackend, MeshBackend
from repro.pm.controller import Knob, OnlineController
from repro.pm.embedding import CacheProbeView, make_state, probe_host
from repro.pm.planner import IntentPlanner
from repro.serve import (DriftingZipfStream, ReplayStream, ServeConfig,
                         ServingRuntime)
from repro.train.loop import LoopConfig, train_loop

V, D, C = 256, 32, 16


def needs(n):
    return pytest.mark.skipif(
        f"len(jax.devices()) < {n}",
        reason=f"needs {n} devices (XLA_FLAGS="
        f"--xla_force_host_platform_device_count={n})")


def mesh_backend(n):
    from repro.launch.mesh import make_model_mesh
    return MeshBackend(make_model_mesh(n))


def pm_cfg():
    # untied embeddings: the delta-refresh gate requires no dense head
    # gradient on the table (tied heads touch every row every step)
    return get_config("smollm-135m", smoke=True).reduced(
        tie_embeddings=False, n_heads=3, n_kv_heads=3)


# --------------------------------------------------------------------------
# plan-ahead candidates (pm/planner.py)
# --------------------------------------------------------------------------
class TestPlanAhead:
    def _planner(self):
        p = IntentPlanner(V, C, n_nodes=2, plan_every=4)
        rng = np.random.default_rng(0)
        for s in range(12):
            p.signal(s, 0, rng.integers(0, V, size=32))
        return p

    def test_candidate_adopt_identical_to_sync_plan(self):
        a, b = self._planner(), self._planner()
        cand = a.plan_candidate(a.plan_window(8))
        adopted = a.adopt(cand, 8)
        sync = b.plan(8)
        assert adopted is not None
        np.testing.assert_array_equal(adopted.cache_ids, sync.cache_ids)
        assert adopted.window == sync.window
        assert adopted.version == sync.version
        assert adopted.predicted_miss_rate == sync.predicted_miss_rate

    def test_candidate_does_not_commit(self):
        p = self._planner()
        v0 = p.plan(4).version
        p.plan_candidate(p.plan_window(8))       # built, never adopted
        assert p.plan(8).version == v0 + 1       # no version hole

    def test_stale_window_rejected(self):
        """A candidate built for the wrong step (the horizon shifted
        between submission and the boundary) is refused — the boundary
        falls back to a synchronous plan()."""
        p = self._planner()
        cand = p.plan_candidate(p.plan_window(6))
        assert p.adopt(cand, 8) is None
        assert p.adopt(None, 8) is None
        assert p.adopt(cand, 6) is not None


# --------------------------------------------------------------------------
# generation-keyed probe view (pm/embedding.py, satellite 1)
# --------------------------------------------------------------------------
class TestCacheProbeView:
    @staticmethod
    def _same(cache_ids, view, tok, m, owner_shards=0, route_capacity=0,
              vocab=V):
        ref = probe_host(cache_ids, tok, m, owner_shards=owner_shards,
                         route_capacity=route_capacity, vocab=vocab)
        got = view.probe(tok, m, owner_shards=owner_shards,
                         route_capacity=route_capacity)
        for f in ref._fields:
            r, g = getattr(ref, f), getattr(got, f)
            if isinstance(r, np.ndarray):
                assert g.dtype == r.dtype, f
                np.testing.assert_array_equal(g, r, err_msg=f)
            else:
                assert g == r, f

    def _check(self, owner_shards=0, route_capacity=0, cap=C, seed=0):
        rng = np.random.default_rng(seed)
        cache_ids = np.sort(rng.choice(V, size=cap, replace=False)) \
            if cap else np.zeros(0, np.int64)
        view = CacheProbeView(cache_ids, V)
        for _ in range(10):
            tok = rng.integers(0, V, size=32)
            for m in (4, 8, 16):
                self._same(cache_ids, view, tok, m, owner_shards,
                           route_capacity)

    def test_matches_probe_host(self):
        self._check()

    def test_matches_probe_host_routed(self):
        self._check(owner_shards=8, route_capacity=2, cap=64, seed=1)

    def test_empty_cache(self):
        self._check(cap=0, seed=2)

    @pytest.mark.parametrize("case", ["planner_padded", "edge_tokens"])
    def test_matches_probe_host_cases(self, case):
        """The planner's cache shape (sorted int32, its unfilled tail
        padded with V) and tokens at the edges of the id space and of
        the cache: 0, V-1, below the smallest cache id, above the
        largest real one."""
        rng = np.random.default_rng(4)
        real = np.sort(rng.choice(np.arange(2, V - 2), size=C - 5,
                                  replace=False))
        cache_ids = np.full(C, V, np.int32)
        cache_ids[:C - 5] = real
        view = CacheProbeView(cache_ids, V)
        lo, hi = int(real[0]), int(real[-1])
        for _ in range(10):
            tok = rng.integers(0, V, size=32)
            if case == "edge_tokens":
                tok[:8] = [0, V - 1, lo - 1, lo, hi, hi + 1, 0, V - 1]
                rng.shuffle(tok)
            for m in (4, 8, 16):
                self._same(cache_ids, view, tok, m)
                self._same(cache_ids, view, tok, m, owner_shards=8,
                           route_capacity=2)

    def test_build_allocates_nothing_vocab_sized(self):
        """A cache generation's view costs O(C): at V = 10 M and C =
        8,192 the build's traced peak stays under 1 MiB (tables over V
        would take tens of MB), and a batch still probes exactly."""
        import tracemalloc
        big_v, cap = 10_000_000, 8192
        rng = np.random.default_rng(5)
        cache_ids = np.sort(rng.choice(big_v, size=cap, replace=False)
                            ).astype(np.int32)
        tracemalloc.start()
        try:
            view = CacheProbeView(cache_ids, big_v)
            _, peak = tracemalloc.get_traced_memory()
        finally:
            tracemalloc.stop()
        assert peak < 1 << 20, peak
        tok = np.concatenate([rng.choice(cache_ids, size=800),
                              rng.integers(0, big_v, size=800)])
        self._same(cache_ids, view, tok, 1024, vocab=big_v)


# --------------------------------------------------------------------------
# delta replica refresh (pm/collectives.py)
# --------------------------------------------------------------------------
class TestDeltaRefresh:
    def _run(self, backend):
        rng = np.random.default_rng(3)
        table = jnp.asarray(rng.normal(size=(V, D)), jnp.float32)
        if hasattr(backend, "mesh"):
            table = backend.place_table(table)
        cache_ids = np.sort(rng.choice(V, size=C, replace=False))
        stale = jnp.asarray(rng.normal(size=(C, D)), jnp.float32)
        touched = np.sort(rng.choice(cache_ids, size=7, replace=False))
        n = 8
        ids = np.full(n, V, np.int32)
        ids[:7] = touched
        slots = np.full(n, C, np.int32)
        slots[:7] = np.searchsorted(cache_ids, touched)
        got = backend.refresh_rows_delta(table, stale, jnp.asarray(ids),
                                         jnp.asarray(slots))
        want = np.array(stale)
        want[np.searchsorted(cache_ids, touched)] = \
            np.asarray(table)[touched]
        np.testing.assert_array_equal(np.asarray(got), want)

    def test_emulated(self):
        self._run(EmulatedBackend(2))

    @pytest.mark.parametrize("n", [pytest.param(2, marks=needs(2)),
                                   pytest.param(8, marks=needs(8))])
    def test_mesh(self, n):
        self._run(mesh_backend(n))


# --------------------------------------------------------------------------
# pipelined training == synchronous training, byte-identical
# --------------------------------------------------------------------------
class TestPrefetchedTrainEquivalence:
    """The tentpole exactness claim: a 50-step trace with the prefetch
    pipeline on (plan-ahead thread, delta refresh, deferred loss blocks)
    is byte-identical to the synchronous loop — same losses, same plan
    and refresh counts."""

    def _trace(self, depth, **kw):
        bus = Telemetry()
        # capacity well above the 64-row delta bucket floor: a 32-token
        # step's touched set must stay a SMALL fraction of the cache or
        # the near-full-delta fallback takes the one full gather instead
        base = dict(steps=50, batch=2, seq=16, pm=True, cache_capacity=256,
                    refresh_every=1, log_every=0, seed=3,
                    pipeline_depth=depth)
        base.update(kw)
        res = train_loop(pm_cfg(), LoopConfig(**base), telemetry=bus)
        return res, bus

    @pytest.mark.parametrize("kernel", [False, True])
    @pytest.mark.parametrize("n_shards", [1, 2])
    def test_emulated(self, n_shards, kernel):
        sync, _ = self._trace(0, n_shards=n_shards, kernel=kernel)
        pipe, bus = self._trace(2, n_shards=n_shards, kernel=kernel)
        assert pipe.losses == sync.losses            # bitwise float eq
        assert pipe.plans == sync.plans
        assert pipe.refreshes == sync.refreshes
        # the pipelined run really took the delta path (not vacuous)
        assert bus.counter_value("train.delta_refreshes") > 0

    @pytest.mark.parametrize("kernel", [False, True])
    @pytest.mark.parametrize("n", [pytest.param(2, marks=needs(2)),
                                   pytest.param(8, marks=needs(8))])
    def test_mesh(self, n, kernel):
        kw = dict(collective="mesh", model_shards=n, kernel=kernel)
        sync, _ = self._trace(0, **kw)
        pipe, bus = self._trace(2, **kw)
        assert pipe.losses == sync.losses
        assert pipe.refreshes == sync.refreshes
        assert bus.counter_value("train.delta_refreshes") > 0

    def test_tied_embeddings_disable_delta_but_stay_exact(self):
        """Tied heads put dense gradients on every table row: the delta
        gate must self-disable (full refresh) and the trace still match."""
        cfg = get_config("smollm-135m", smoke=True)
        assert cfg.tie_embeddings
        base = dict(steps=20, batch=2, seq=16, pm=True, cache_capacity=256,
                    refresh_every=1, log_every=0, seed=3)
        bus = Telemetry()
        sync = train_loop(cfg, LoopConfig(**base, pipeline_depth=0))
        pipe = train_loop(cfg, LoopConfig(**base, pipeline_depth=2),
                          telemetry=bus)
        assert pipe.losses == sync.losses
        assert bus.counter_value("train.delta_refreshes") == 0


# --------------------------------------------------------------------------
# pipelined serving == sequential serving
# --------------------------------------------------------------------------
class TestPipelinedServeEquivalence:
    """The N-deep admission pipeline plus the tenure staging prefetch is
    a pure wall-clock transform: served values, requeue sets, replans
    and miss traces all match the depth-0 sequential loop on a drifting
    replay."""

    def _run(self, replay, depth):
        rng = np.random.default_rng(0)
        table = rng.normal(size=(2048, 8)).astype(np.float32)
        cfg = ServeConfig(vocab=2048, batch_requests=16,
                          keys_per_request=8, cache_capacity=256,
                          replan_every=6, pipeline_depth=depth)
        rt = ServingRuntime(table, cfg)
        return rt.run(replay, rounds=30, collect_outputs=True)

    def test_depths_identical_to_sequential(self):
        live = DriftingZipfStream(2048, 8, zipf_a=1.2, arrival_rate=16,
                                  scenario="rotate", rotate_every=10,
                                  seed=5)
        replay = ReplayStream.record(live, 50)
        base = self._run(replay, 0)
        assert base.zero_served == 0
        for depth in (1, 2, 4):
            got = self._run(replay, depth)
            assert got.served == base.served
            assert got.requeues == base.requeues
            assert got.replans == base.replans
            assert got.replan_rounds == base.replan_rounds
            assert got.miss_trace == base.miss_trace
            assert got.zero_served == 0
            assert set(got.outputs) == set(base.outputs)
            for rid in base.outputs:
                np.testing.assert_array_equal(got.outputs[rid],
                                              base.outputs[rid])

    def test_double_buffer_alias_maps_to_depth(self):
        """Back-compat: the PR-6 one-slot flag is now an alias for
        pipeline_depth 1/0, readable as a derived property."""
        table = np.zeros((64, 4), np.float32)
        rt1 = ServingRuntime(table, ServeConfig(
            vocab=64, cache_capacity=16, double_buffer=True))
        rt0 = ServingRuntime(table, ServeConfig(
            vocab=64, cache_capacity=16, double_buffer=False))
        assert rt1.pipeline_depth == 1 and rt1.double_buffer
        assert rt0.pipeline_depth == 0 and not rt0.double_buffer
        rt2 = ServingRuntime(table, ServeConfig(
            vocab=64, cache_capacity=16, pipeline_depth=4))
        assert rt2.pipeline_depth == 4 and rt2.double_buffer


# --------------------------------------------------------------------------
# controller event schema (satellite 2)
# --------------------------------------------------------------------------
class TestForceEventSchema:
    def test_ctl_force_always_carries_target(self):
        """Every ctl.force event renders on the report knob timeline:
        knob, value, cause AND the triggering target ride every emit
        (the serve runtime's overlap calibration goes through
        force_at_least like every other signal rule)."""
        bus = Telemetry()
        ctl = OnlineController(
            [Knob("pipeline_depth", (0, 1, 2, 4), prefer_low=True)],
            telemetry=bus)
        assert ctl.force_at_least("pipeline_depth", 2,
                                  cause="overlap") == 2
        assert ctl.force_at_least("pipeline_depth", 2) is None  # no-op
        evs = bus.events("ctl.force")
        assert len(evs) == 1
        for ev in evs:
            for f in ("knob", "value", "cause", "target"):
                assert f in ev, f
        assert evs[0]["target"] == 2 and evs[0]["cause"] == "overlap"

    def test_runtime_emits_no_bare_force(self):
        """Grep-level guard: every ctl.force on the bus from a serve run
        carries the unified schema."""
        rng = np.random.default_rng(0)
        table = rng.normal(size=(512, 8)).astype(np.float32)
        stream = DriftingZipfStream(512, 8, zipf_a=1.1, arrival_rate=8,
                                    seed=1)
        bus = Telemetry()
        cfg = ServeConfig(vocab=512, batch_requests=8, keys_per_request=8,
                          cache_capacity=64, replan_every=4)
        ServingRuntime(table, cfg, telemetry=bus).run(stream, rounds=12)
        for ev in bus.events("ctl.force"):
            assert {"knob", "value", "cause", "target"} <= set(ev)
