"""Tests for the collective-backend layer (DESIGN.md §10): the mesh-real
`shard_map` data path vs the emulated single-device reference vs a plain
dense lookup, across shard counts, overflow, kernel on/off, and full
train-loop loss traces.

The mesh cases need a multi-device host — CI provides one with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the mesh smoke
job; in the full tier-1 run `tests/test_dryrun.py`'s import-time flag
provides 512); on a single-device host they skip.  The skip conditions
are string-form on purpose: pytest evaluates those lazily at run time,
so collecting this module never initializes the jax backend (which would
freeze the device count before other modules' import-time XLA_FLAGS take
effect)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr

from repro.kernels import ops
from repro.pm.collectives import (EMULATED, EmulatedBackend, MeshBackend,
                                  route_block_cap)
from repro.pm.embedding import (combine_miss_buffer, make_state, pm_lookup,
                                plain_lookup, plain_serve_lookup,
                                planned_serve_lookup, probe_host,
                                serve_lookup, shard_partial_sum)

V, D, C = 256, 32, 16


def needs(n):
    return pytest.mark.skipif(
        f"len(jax.devices()) < {n}",
        reason=f"needs {n} devices (XLA_FLAGS="
        f"--xla_force_host_platform_device_count={n})")


SHARD_COUNTS = [pytest.param(1),
                pytest.param(2, marks=needs(2)),
                pytest.param(8, marks=needs(8))]


def mesh_backend(n: int) -> MeshBackend:
    from repro.launch.mesh import make_model_mesh
    return MeshBackend(make_model_mesh(n))


def setup(seed=0, cache_ids=None):
    rng = np.random.default_rng(seed)
    table = jnp.asarray(rng.normal(size=(V, D)), dtype=jnp.float32)
    if cache_ids is None:
        cache_ids = np.sort(rng.choice(V, size=C, replace=False))
    cache_ids = jnp.asarray(cache_ids, dtype=jnp.int32)
    return table, cache_ids, rng


class TestEmulatedBackendRefactor:
    """The refactor is behavior-preserving: the explicit EmulatedBackend
    is bitwise the legacy n_shards/kernel paths (single device)."""

    def test_default_backend_is_emulated_reference(self):
        table, cache_ids, rng = setup()
        st = make_state(table, cache_ids)
        tokens = jnp.asarray(rng.integers(0, V, size=(4, 8)), jnp.int32)
        a = pm_lookup(table, st.cache_ids, st.cache_rows, tokens, 16)
        b = pm_lookup(table, st.cache_ids, st.cache_rows, tokens, 16,
                      False, False, EMULATED)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_shard_partial_sum_alias(self):
        """The legacy entry point is the EmulatedBackend gather (barrier
        partials preserved: same rows for every shard count)."""
        table, _, rng = setup()
        ids = jnp.asarray(rng.integers(0, V, size=24), jnp.int32)
        direct = EmulatedBackend(4).gather_rows(table, ids)
        legacy = shard_partial_sum(table, ids, 4)
        np.testing.assert_array_equal(np.asarray(direct),
                                      np.asarray(legacy))
        np.testing.assert_array_equal(
            np.asarray(direct), np.asarray(jnp.take(table, ids, axis=0)))

    def test_one_shared_data_path(self):
        """All three managed variants produce identical rows for the same
        probe — they are thin wrappers over `combine_miss_buffer`."""
        table, cache_ids, rng = setup()
        st = make_state(table, cache_ids)
        tokens = rng.integers(0, V, size=(4, 6)).astype(np.int32)
        # capacity T: every unique miss fits, so all four variants agree
        # with the dense lookup too (no overflow semantics in play)
        hp = probe_host(np.asarray(cache_ids), tokens.reshape(-1), 24)
        shared = combine_miss_buffer(
            EMULATED, table, st.cache_rows, jnp.asarray(hp.hit),
            jnp.asarray(hp.cache_slot), jnp.asarray(hp.buf_ids),
            jnp.asarray(hp.buf_slot))
        planned = planned_serve_lookup(
            table, st.cache_rows, jnp.asarray(hp.buf_ids),
            jnp.asarray(hp.hit.astype(np.int32)),
            jnp.asarray(hp.cache_slot), jnp.asarray(hp.buf_slot))
        srv = serve_lookup(table, st.cache_ids, st.cache_rows,
                           jnp.asarray(tokens), 24)
        trn = pm_lookup(table, st.cache_ids, st.cache_rows,
                        jnp.asarray(tokens), 24)
        np.testing.assert_array_equal(np.asarray(shared),
                                      np.asarray(planned))
        np.testing.assert_array_equal(
            np.asarray(shared).reshape(4, 6, D), np.asarray(srv.out))
        np.testing.assert_array_equal(
            np.asarray(shared).reshape(4, 6, D), np.asarray(trn))

    def test_refresh_rows_pads_zero(self):
        table, _, _ = setup()
        ids = jnp.asarray([3, 7, V, V], jnp.int32)   # two pad slots
        rows = EMULATED.refresh_rows(table, ids)
        np.testing.assert_allclose(np.asarray(rows[:2]),
                                   np.asarray(table[jnp.asarray([3, 7])]))
        np.testing.assert_array_equal(np.asarray(rows[2:]), 0.0)


class TestMeshBackendEquivalence:
    """MeshBackend vs EmulatedBackend vs plain dense lookup, across shard
    counts, overflow slots and kernel on/off (the ISSUE 4 acceptance
    matrix)."""

    @pytest.mark.parametrize("n", SHARD_COUNTS)
    @pytest.mark.parametrize("kernel", [False, True])
    def test_forward_matches_emulated_and_plain(self, n, kernel):
        table, cache_ids, rng = setup()
        be = mesh_backend(n)
        ts = be.place_table(table)
        st = make_state(ts, cache_ids, be)
        tokens = jnp.asarray(rng.integers(0, V, size=(4, 8)), jnp.int32)
        out = pm_lookup(ts, st.cache_ids, st.cache_rows, tokens, 64,
                        False, kernel, be)
        emu = pm_lookup(table, st.cache_ids,
                        EMULATED.refresh_rows(table, st.cache_ids),
                        tokens, 64, False, kernel)
        exp = plain_lookup(table, tokens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   rtol=1e-6)
        np.testing.assert_allclose(np.asarray(out), np.asarray(emu),
                                   rtol=1e-6)

    @pytest.mark.parametrize("n", SHARD_COUNTS)
    @pytest.mark.parametrize("kernel", [False, True])
    def test_backward_matches_emulated_and_plain(self, n, kernel):
        table, cache_ids, rng = setup()
        be = mesh_backend(n)
        ts = be.place_table(table)
        st = make_state(ts, cache_ids, be)
        tokens = jnp.asarray(rng.integers(0, V, size=(2, 12)), jnp.int32)

        def loss(t, backend, k):
            rows = st.cache_rows if backend is not None else \
                EMULATED.refresh_rows(table, st.cache_ids)
            out = pm_lookup(t, st.cache_ids, rows, tokens, 16, False, k,
                            backend)
            return jnp.sum(out ** 2)

        g_mesh = jax.grad(lambda t: loss(t, be, kernel))(ts)
        g_emu = jax.grad(lambda t: loss(t, None, kernel))(table)
        g_ref = jax.grad(
            lambda t: jnp.sum(plain_lookup(t, tokens) ** 2))(table)
        np.testing.assert_allclose(np.asarray(g_mesh), np.asarray(g_ref),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(g_mesh), np.asarray(g_emu),
                                   rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("n", SHARD_COUNTS)
    def test_overflow_fallback_and_strict_zeros(self, n):
        """Overflow slots behave identically on the mesh: non-strict falls
        back to the dense (backend) gather, strict reads zeros."""
        table, _, rng = setup()
        cache_ids = jnp.asarray(np.arange(100, 100 + C), jnp.int32)
        be = mesh_backend(n)
        ts = be.place_table(table)
        st = make_state(ts, cache_ids, be)
        tokens = jnp.asarray([[3, 5, 7, 9, 3, 5]], jnp.int32)  # 4 uniq miss
        out = pm_lookup(ts, st.cache_ids, st.cache_rows, tokens, 2,
                        False, False, be)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(plain_lookup(table, tokens)),
                                   rtol=1e-6)
        strict = np.asarray(pm_lookup(ts, st.cache_ids, st.cache_rows,
                                      tokens, 2, True, False, be))
        strict_emu = np.asarray(pm_lookup(
            table, st.cache_ids, EMULATED.refresh_rows(table, st.cache_ids),
            tokens, 2, True))
        np.testing.assert_allclose(strict, strict_emu, rtol=1e-6)

    @pytest.mark.parametrize("n", SHARD_COUNTS)
    def test_serve_lookup_flags_match(self, n):
        table, _, rng = setup(cache_ids=np.arange(100, 100 + C))
        cache_ids = jnp.asarray(np.arange(100, 100 + C), jnp.int32)
        be = mesh_backend(n)
        ts = be.place_table(table)
        st = make_state(ts, cache_ids, be)
        tokens = jnp.asarray([[3, 5, 7, 9]], jnp.int32)
        r_mesh = serve_lookup(ts, st.cache_ids, st.cache_rows, tokens, 2,
                              backend=be)
        r_emu = serve_lookup(table, st.cache_ids,
                             EMULATED.refresh_rows(table, st.cache_ids),
                             tokens, 2)
        np.testing.assert_allclose(np.asarray(r_mesh.out),
                                   np.asarray(r_emu.out), rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(r_mesh.overflow),
                                      np.asarray(r_emu.overflow))
        assert int(r_mesh.n_miss) == int(r_emu.n_miss)

    @pytest.mark.parametrize("n", SHARD_COUNTS)
    def test_plain_serve_lookup_dense_psum(self, n):
        table, _, rng = setup()
        be = mesh_backend(n)
        ts = be.place_table(table)
        tokens = jnp.asarray(rng.integers(0, V, size=(3, 5)), jnp.int32)
        out = plain_serve_lookup(ts, tokens, backend=be)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(plain_lookup(table, tokens)),
                                   rtol=1e-6)

    @pytest.mark.parametrize("n", SHARD_COUNTS)
    def test_refresh_grouped_allgather(self, n):
        """Replica sync through the mesh backend == the emulated gather,
        pad slots (id V) zero."""
        table, cache_ids, _ = setup()
        ids = jnp.concatenate([cache_ids[:C - 2],
                               jnp.full((2,), V, jnp.int32)])
        be = mesh_backend(n)
        ts = be.place_table(table)
        mesh_rows = be.refresh_rows(ts, ids)
        emu_rows = EMULATED.refresh_rows(table, ids)
        np.testing.assert_allclose(np.asarray(mesh_rows),
                                   np.asarray(emu_rows), rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(mesh_rows[-2:]), 0.0)

    @needs(8)
    def test_vocab_divisibility_enforced(self):
        table = jnp.zeros((V + 4, D))   # 260 % 8 != 0
        be = mesh_backend(8)
        with pytest.raises(ValueError, match="divide"):
            be.gather_rows(table, jnp.asarray([1], jnp.int32))


class TestMeshTrainLoop:
    """The whole training stack over the mesh backend: identical losses
    to the single-device managed path, zero overflow fallbacks."""

    @needs(8)
    def test_50_step_loss_trace_matches_single_device(self):
        from repro.configs.registry import get_config
        from repro.train.loop import LoopConfig, train_loop
        cfg = get_config("smollm-135m", smoke=True)
        base = dict(steps=50, batch=4, seq=32, pm=True, cache_capacity=64,
                    log_every=0, seed=3)
        r_emu = train_loop(cfg, LoopConfig(**base))
        r_mesh = train_loop(cfg, LoopConfig(**base, collective="mesh",
                                            model_shards=8))
        np.testing.assert_allclose(r_mesh.losses, r_emu.losses,
                                   rtol=1e-4, atol=1e-5)
        assert r_mesh.overflows == 0
        assert r_mesh.plans >= 1

    @needs(8)
    @pytest.mark.slow
    def test_200_step_mesh_zero_overflow(self):
        """ISSUE 4 acceptance: the intent-derived per-shard capacity is
        exact on the mesh path too — 200 steps, no dense fallback."""
        from repro.configs.registry import get_config
        from repro.train.loop import LoopConfig, train_loop
        cfg = get_config("smollm-135m", smoke=True)
        res = train_loop(cfg, LoopConfig(steps=200, batch=4, seq=32,
                                         pm=True, cache_capacity=64,
                                         refresh_every=4, log_every=0,
                                         seed=5, collective="mesh",
                                         model_shards=8))
        assert res.overflows == 0
        assert res.plans > 1
        assert all(np.isfinite(res.losses))


class TestRoutedMissPath:
    """ISSUE 6 unit matrix: the destination-compacted routed primitives
    against the replicated-psum legacy path and the dense reference."""

    def test_route_block_cap_rule(self):
        # 2x-headroom even split, pow2-rounded, clamped to m
        assert route_block_cap(16, 1) == 16
        assert route_block_cap(16, 2) == 16
        assert route_block_cap(16, 8) == 4
        assert route_block_cap(24, 8) == 8
        assert route_block_cap(256, 8) == 64
        assert route_block_cap(1, 8) == 1

    @pytest.mark.parametrize("n", SHARD_COUNTS)
    @pytest.mark.parametrize("kernel", [False, True])
    def test_routed_gather_matches_take(self, n, kernel):
        table, _, rng = setup()
        be = mesh_backend(n)
        ts = be.place_table(table)
        M, nv = 24, 17
        ids = np.full(M, V, np.int32)
        ids[:nv] = np.sort(rng.choice(V, nv, replace=False))
        for cap in (0, M):    # derived cap (cond arm for n=8) and pinned
            out = be.gather_rows_routed(ts, jnp.asarray(ids),
                                        jnp.int32(nv), route_cap=cap,
                                        kernel=kernel)
            np.testing.assert_allclose(
                np.asarray(out[:nv]),
                np.asarray(jnp.take(table, jnp.asarray(ids[:nv]), axis=0)),
                rtol=1e-6)
            # pad slots come back ZERO (stronger than gather_rows, which
            # returns row `pad_id` — callers read neither)
            np.testing.assert_array_equal(np.asarray(out[nv:]), 0.0)

    @pytest.mark.parametrize("n", SHARD_COUNTS)
    def test_routed_gather_skew_falls_back_to_psum(self, n):
        """Worst-case skew — every miss owned by shard 0 — exceeds a tiny
        pinned cap and must take the replicated-psum cond arm, still
        byte-correct with zero pad slots."""
        table, _, _ = setup()
        be = mesh_backend(n)
        ts = be.place_table(table)
        M, nv = 32, 20
        ids = np.full(M, V, np.int32)
        ids[:nv] = np.arange(nv)
        out = be.gather_rows_routed(ts, jnp.asarray(ids), jnp.int32(nv),
                                    route_cap=8)
        np.testing.assert_allclose(np.asarray(out[:nv]),
                                   np.asarray(table[:nv]), rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(out[nv:]), 0.0)

    @pytest.mark.parametrize("n", SHARD_COUNTS)
    @pytest.mark.parametrize("segmented", [False, True])
    def test_routed_scatter_matches_psum_and_dense(self, n, segmented):
        table, _, rng = setup()
        be = mesh_backend(n)
        T = 40
        tok = jnp.asarray(rng.integers(0, V, T), jnp.int32)
        g = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
        if segmented:
            ids, gg = ops.segment_rows(tok, g, n_slots=T, pad_id=V)
            args = (ids, gg.astype(g.dtype))
        else:
            args = (tok, g)
        routed = be.scatter_row_grads(*args, V, segmented=segmented)
        legacy = be.scatter_row_grads_psum(*args, V, segmented=segmented)
        dense = jnp.zeros((V, D), jnp.float32).at[tok].add(g)
        np.testing.assert_allclose(np.asarray(routed), np.asarray(dense),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(routed), np.asarray(legacy),
                                   rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("n", SHARD_COUNTS)
    @pytest.mark.parametrize("kernel", [False, True])
    def test_update_rows_matches_emulated(self, n, kernel):
        """The on-shard fused AdaGrad through the all_to_all router ==
        the single-device emulated update, untouched rows bit-identical."""
        table, _, rng = setup()
        accum = jnp.asarray(rng.uniform(0.01, 1.0, size=(V, D)),
                            jnp.float32)
        T = 48
        tok = jnp.asarray(rng.integers(0, V, T), jnp.int32)
        g = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
        seg_ids, seg_g = ops.segment_rows(tok, g, n_slots=T, pad_id=V)
        seg_g = seg_g.astype(jnp.float32)
        be = mesh_backend(n)
        mt, ma = be.update_rows(be.place_table(table),
                                be.place_table(accum), seg_ids, seg_g,
                                lr=0.05, kernel=kernel)
        et, ea = EMULATED.update_rows(table, accum, seg_ids, seg_g,
                                      lr=0.05, kernel=kernel)
        np.testing.assert_allclose(np.asarray(mt), np.asarray(et),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(ma), np.asarray(ea),
                                   rtol=1e-5, atol=1e-6)
        mask = np.ones(V, bool)
        mask[np.asarray(tok)] = False
        np.testing.assert_array_equal(np.asarray(mt)[mask],
                                      np.asarray(table)[mask])


def _sorts_in(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "sort":
            n += 1
        for v in eqn.params.values():
            vs = v if isinstance(v, (list, tuple)) else [v]
            for x in vs:
                if isinstance(x, ClosedJaxpr):
                    n += _sorts_in(x.jaxpr)
                elif isinstance(x, Jaxpr):
                    n += _sorts_in(x)
    return n


def _dense_rows_in(jaxpr, vocab: int) -> list:
    """Shapes of broadcast-materialized buffers with a leading dim >= the
    full vocab — the dense (V, D) partials the routed path must never
    build.  `cond` bodies are exempt: the skew fallback arm is allowed to
    be dense."""
    bad = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            continue
        if eqn.primitive.name == "broadcast_in_dim":
            shp = eqn.outvars[0].aval.shape
            if shp and isinstance(shp[0], int) and shp[0] >= vocab:
                bad.append(shp)
        for v in eqn.params.values():
            vs = v if isinstance(v, (list, tuple)) else [v]
            for x in vs:
                if isinstance(x, ClosedJaxpr):
                    bad += _dense_rows_in(x.jaxpr, vocab)
                elif isinstance(x, Jaxpr):
                    bad += _dense_rows_in(x, vocab)
    return bad


def _fused_setup():
    from repro.configs.registry import get_config
    from repro.models.model import init_model
    from repro.train.steps import make_opt_init
    cfg = get_config("smollm-135m", smoke=True).reduced(
        tie_embeddings=False, n_heads=3, n_kv_heads=3)
    rng = np.random.default_rng(0)
    params = init_model(cfg, jax.random.PRNGKey(0))
    opt = make_opt_init("adagrad")(params)
    tokens = rng.integers(0, cfg.vocab_size, size=(2, 16)).astype(np.int32)
    cache_ids = np.sort(rng.choice(cfg.vocab_size, 32,
                                   replace=False)).astype(np.int32)
    return cfg, params, opt, tokens, cache_ids


def _fused_batch(tokens, cache_ids, emb, be=None):
    st = make_state(emb, jnp.asarray(cache_ids), be)
    return {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(tokens),
            "pm_cache_ids": st.cache_ids, "pm_cache_rows": st.cache_rows}


class TestMeshFusedStep:
    """ISSUE 6 tentpole acceptance: the managed train step over the mesh
    backend takes the routed fused sparse path — equal losses/params to
    the emulated fused AND emulated dense steps, exactly one sort in its
    jaxpr, no dense (V, D) buffer outside the fallback cond, and donated
    sharded table/accumulator."""

    M = 16

    def _step(self, cfg, kernel, be=None):
        from repro.train.steps import make_train_step
        return make_train_step(cfg, pm_miss_capacity=self.M,
                               pm_kernel=kernel, pm_backend=be, lr=0.05)

    def _placed(self, be, params, opt):
        mp = dict(params, embed=be.place_table(params["embed"]))
        mo = type(opt)(dict(opt.accum,
                            embed=be.place_table(opt.accum["embed"])))
        return mp, mo

    @pytest.mark.parametrize("n", SHARD_COUNTS)
    @pytest.mark.parametrize("kernel", [False, True])
    def test_matches_emulated_fused_and_dense(self, n, kernel):
        cfg, params, opt, tokens, cache_ids = _fused_setup()
        emb = params["embed"]
        l_dense, p_dense, _ = self._step(cfg, False)(
            params, opt, _fused_batch(tokens, cache_ids, emb))
        l_fused, p_fused, s_fused = self._step(cfg, True)(
            params, opt, _fused_batch(tokens, cache_ids, emb))
        assert np.allclose(float(l_fused), float(l_dense), rtol=1e-5)
        be = mesh_backend(n)
        mp, mo = self._placed(be, params, opt)
        lm, pm, sm = self._step(cfg, kernel, be)(
            mp, mo, _fused_batch(tokens, cache_ids, mp["embed"], be))
        assert np.allclose(float(lm), float(l_fused), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(pm["embed"]),
                                   np.asarray(p_fused["embed"]),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(pm["embed"]),
                                   np.asarray(p_dense["embed"]),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(sm.accum["embed"]),
                                   np.asarray(s_fused.accum["embed"]),
                                   atol=1e-5)

    @needs(8)
    @pytest.mark.parametrize("kernel", [False, True])
    def test_one_sort_and_no_dense_vocab_buffer(self, kernel):
        cfg, params, opt, tokens, cache_ids = _fused_setup()
        be = mesh_backend(8)
        mp, mo = self._placed(be, params, opt)
        batch = _fused_batch(tokens, cache_ids, mp["embed"], be)
        jaxpr = jax.make_jaxpr(self._step(cfg, kernel, be))(mp, mo, batch)
        assert _sorts_in(jaxpr.jaxpr) == 1
        assert _dense_rows_in(jaxpr.jaxpr, cfg.vocab_size) == []

    @needs(2)
    def test_donation_engages_re_feed_raises(self):
        """The guard `train.loop` relies on: donated sharded buffers are
        really consumed, so re-feeding the pre-step table is an error —
        the loop must thread the returned arrays, never the originals."""
        cfg, params, opt, tokens, cache_ids = _fused_setup()
        be = mesh_backend(2)
        mp, mo = self._placed(be, params, opt)
        batch = _fused_batch(tokens, cache_ids, mp["embed"], be)
        step = jax.jit(self._step(cfg, False, be), donate_argnums=(0, 1))
        _, new_p, _ = step(mp, mo, batch)
        jax.block_until_ready(new_p["embed"])
        with pytest.raises(RuntimeError):
            np.asarray(mp["embed"])

    @needs(8)
    def test_50_step_fused_trace_matches_emulated_dense(self):
        """Untied smoke config: the mesh loop runs the routed FUSED
        optimizer while the emulated loop runs the dense reference —
        identical loss traces, zero overflow fallbacks."""
        from repro.configs.registry import get_config
        from repro.train.loop import LoopConfig, train_loop
        cfg = get_config("smollm-135m", smoke=True).reduced(
            tie_embeddings=False, n_heads=3, n_kv_heads=3)
        base = dict(steps=50, batch=4, seq=32, pm=True, cache_capacity=64,
                    log_every=0, seed=3)
        r_emu = train_loop(cfg, LoopConfig(**base))
        r_mesh = train_loop(cfg, LoopConfig(**base, collective="mesh",
                                            model_shards=8))
        np.testing.assert_allclose(r_mesh.losses, r_emu.losses,
                                   rtol=1e-4, atol=1e-5)
        assert r_mesh.overflows == 0


class TestPerOwnerAdmission:
    """Serving admission for the routed miss path: `probe_host` flags
    per-owner overflow (DESIGN.md §12) and the planner publishes the
    matching `route_capacity` bound."""

    def test_probe_flags_per_owner_overflow(self):
        cache = np.full(4, V, np.int32)          # empty cache: all miss
        tok = np.asarray([1, 2, 3, 100, 3], np.int32)
        base = probe_host(cache, tok, 8)
        assert not base.overflow.any()
        # owner blocks of 32: ids {1,2,3} are owner 0 ranks 0..2, id 100
        # is owner 3 rank 0 — cap 2 overflows exactly id 3's tokens
        pr = probe_host(cache, tok, 8, owner_shards=8, route_capacity=2,
                        vocab=V)
        np.testing.assert_array_equal(np.asarray(pr.overflow), tok == 3)
        np.testing.assert_array_equal(np.asarray(pr.buf_ids),
                                      np.asarray(base.buf_ids))
        assert pr.n_miss == base.n_miss
        ok = probe_host(cache, tok, 8, owner_shards=8, route_capacity=3,
                        vocab=V)
        assert not ok.overflow.any()

    def test_probe_per_owner_off_without_mesh_args(self):
        cache = np.full(4, V, np.int32)
        tok = np.arange(20, dtype=np.int32)      # 20 misses in owner 0
        pr = probe_host(cache, tok, 32)          # no owner accounting
        assert not pr.overflow.any()

    def test_planner_publishes_route_capacity(self):
        from repro.pm.planner import IntentPlanner
        pl = IntentPlanner(vocab_size=256, cache_capacity=4, n_shards=2,
                           owner_shards=8)
        # ids 0..19 all live in owner 0 (block 32): the worst
        # per-(step, owner) unique-miss count is 20
        for step in range(4):
            pl.signal(step, 0, np.arange(20))
            pl.signal(step, 1, np.asarray([40, 41]))
        plan = pl.plan(0)
        assert plan.route_capacity >= 20
        # without owner accounting the field stays 0 (non-mesh backends)
        pl0 = IntentPlanner(vocab_size=256, cache_capacity=4, n_shards=2)
        pl0.signal(0, 0, np.asarray([1, 2]))
        assert pl0.plan(0).route_capacity == 0


class TestMeshServingRuntime:
    """End-to-end serving over the mesh backend: every served request
    gets exactly its table rows through the real psum data path."""

    @needs(8)
    def test_served_rows_exact_over_mesh(self):
        from repro.serve import (DriftingZipfStream, ReplayStream,
                                 ServeConfig, ServingRuntime)
        rng = np.random.default_rng(0)
        table = rng.normal(size=(2048, 8)).astype(np.float32)
        live = DriftingZipfStream(2048, 8, zipf_a=1.2, arrival_rate=16,
                                  scenario="rotate", rotate_every=10,
                                  seed=5)
        replay = ReplayStream.record(live, 40)
        rid_to_keys = {r.rid: r.keys for per in replay.per_round
                       for r in per}
        cfg = ServeConfig(vocab=2048, batch_requests=16,
                          keys_per_request=8, cache_capacity=256,
                          replan_every=6, collective="mesh",
                          model_shards=8)
        rt = ServingRuntime(table, cfg)
        res = rt.run(replay, rounds=20, collect_outputs=True)
        assert res.zero_served == 0
        assert res.served > 100
        for rid, rows in res.outputs.items():
            np.testing.assert_allclose(rows, table[rid_to_keys[rid]],
                                       rtol=1e-6)


class TestMeshServingCounters:
    """The mesh's own decisions are counted on the host, per batch and
    per refresh: tokens only the per-owner bound flagged
    (``serve.route_overflow_tokens``) and routed gathers that take the
    psum arm (``serve.route_fallbacks``); the one-chip path writes
    neither."""

    VOCAB = 256                          # four owners of 64 rows

    def _runtime(self, table, collective="mesh", **kw):
        from repro.serve import ServeConfig, ServingRuntime
        knobs = dict(batch_requests=4, keys_per_request=8,
                     cache_capacity=8, replan_every=1, pipeline_depth=0,
                     refresh_every=0, summary=False)
        knobs.update(kw)
        if collective == "mesh":
            knobs.update(collective="mesh", model_shards=4)
        return ServingRuntime(table, ServeConfig(vocab=self.VOCAB, **knobs))

    @staticmethod
    def _stream(keys):
        from repro.serve import ReplayStream
        from repro.serve.requests import ServeRequest
        return ReplayStream([[ServeRequest(i, np.asarray(k, np.int32))
                              for i, k in enumerate(keys)]])

    @needs(4)
    def test_a_batch_crowding_one_owner_counts_and_is_served_exactly(
            self, monkeypatch):
        """Four requests of eight distinct keys, all owned by shard 0: the
        cache (8 rows) holds ids 0-7, so ids 8-31 miss and ids 24-31 rank
        16-23 within their owner, past a per-owner bound of 16 (the
        global bound, 64, flags nothing).  Request 3 is requeued, then
        served alone from a replanned cache, every row exact."""
        from repro.pm.planner import IntentPlanner
        monkeypatch.setattr(IntentPlanner, "_route_capacity",
                            lambda self, keys, steps, hot: 16)
        table = np.random.default_rng(0).normal(
            size=(self.VOCAB, 8)).astype(np.float32)
        keys = [np.arange(8 * i, 8 * i + 8) for i in range(4)]
        rt = self._runtime(table)
        res = rt.run(self._stream(keys), rounds=4, warmup_backlog=1,
                     collect_outputs=True)
        t = rt.telemetry
        assert t.counter_value("serve.route_overflow_tokens") == 8
        assert res.requeues == 1 and res.zero_served == 0
        assert sorted(res.outputs) == [0, 1, 2, 3]
        for rid, rows in res.outputs.items():
            np.testing.assert_array_equal(rows, table[keys[rid]])

    @needs(4)
    def test_a_refresh_crowding_one_owner_counts_one_fallback(self):
        """A cache generation of 64 ids, all owned by shard 0, is twice
        the routed block of a 64-slot refresh (`route_block_cap(64, 4)`
        = 32): the refresh takes the psum arm, counted once, and its rows
        are the table's."""
        table = np.random.default_rng(1).normal(
            size=(self.VOCAB, 8)).astype(np.float32)
        keys = [np.arange(16 * i, 16 * i + 16) for i in range(4)]
        rt = self._runtime(table, keys_per_request=16, cache_capacity=64)
        res = rt.run(self._stream(keys), rounds=2, warmup_backlog=1,
                     collect_outputs=True)
        assert res.refreshes == 1
        assert rt.telemetry.counter_value("serve.route_fallbacks") == 1
        np.testing.assert_array_equal(rt._cache_ids_np, np.arange(64))
        np.testing.assert_array_equal(np.asarray(rt._cache_rows),
                                      table[:64])
        for rid, rows in res.outputs.items():
            np.testing.assert_array_equal(rows, table[keys[rid]])

    def test_the_one_chip_path_writes_neither_counter(self):
        table = np.random.default_rng(2).normal(
            size=(self.VOCAB, 8)).astype(np.float32)
        keys = [np.arange(8 * i, 8 * i + 8) for i in range(4)]
        rt = self._runtime(table, collective="emulated")
        res = rt.run(self._stream(keys), rounds=4, warmup_backlog=1,
                     collect_outputs=True)
        assert res.served == 4
        counters = rt.telemetry.snapshot()["counters"]
        assert "serve.route_overflow_tokens" not in counters
        assert "serve.route_fallbacks" not in counters
