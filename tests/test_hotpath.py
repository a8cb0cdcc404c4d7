"""Hot-path regression tests for the single-sort managed step (ISSUE 5):

  * jaxpr inspection — the jitted managed train step contains EXACTLY one
    `sort` primitive (the step residual), kernel path on or off: the
    forward compaction, backward pre-sum and fused sparse optimizer all
    reuse it instead of re-sorting;
  * multi-row (block_r, block_d) kernel tiles vs the pure-jnp oracle over
    odd shapes (rows not a multiple of block_r, feature dims that are not
    lane-aligned and are padded, never shrunk);
  * managed lookup fwd+bwd equivalence across kernel on/off and emulated
    shard counts {1, 2, 8};
  * the measured block autotuner: override precedence and per-key caching.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr

from repro.kernels import blocking, ops, ref
from repro.kernels.adagrad_rows import adagrad_row_update
from repro.kernels.embed_gather import embed_gather
from repro.kernels.pm_forward import (pm_combine, probe_and_compact,
                                      step_residual)
from repro.kernels.scatter_rows import scatter_rows
from repro.pm.collectives import EmulatedBackend
from repro.pm.embedding import make_state, plain_lookup, pm_lookup


def _count_sorts(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "sort":
            n += 1
        for v in eqn.params.values():
            vs = v if isinstance(v, (list, tuple)) else [v]
            for x in vs:
                if isinstance(x, ClosedJaxpr):
                    n += _count_sorts(x.jaxpr)
                elif isinstance(x, Jaxpr):
                    n += _count_sorts(x)
    return n


class TestSingleSortStep:
    """The regression this PR exists to prevent: the managed train step
    used to run three independent argsorts over the same token ids
    (forward probe/compact, backward segment, optimizer row dedup)."""

    @pytest.mark.parametrize("kernel", [False, True])
    def test_managed_train_step_has_exactly_one_sort(self, kernel):
        from repro.configs.registry import get_config
        from repro.data.batches import make_batch
        from repro.models.model import init_model
        from repro.train.steps import make_opt_init, make_train_step
        cfg = get_config("smollm-135m", smoke=True).reduced(
            tie_embeddings=False, n_heads=3, n_kv_heads=3)
        params = init_model(cfg, jax.random.PRNGKey(0))
        opt = make_opt_init("adagrad")(params)
        batch = make_batch(cfg, 2, 16, np.random.default_rng(0))
        C = 32
        batch = dict(batch,
                     pm_cache_ids=jnp.asarray(np.arange(C), jnp.int32),
                     pm_cache_rows=jnp.zeros((C, cfg.d_model), jnp.float32))
        step = make_train_step(cfg, pm_miss_capacity=16, pm_kernel=kernel)
        jaxpr = jax.make_jaxpr(step)(params, opt, batch)
        assert _count_sorts(jaxpr.jaxpr) == 1

    def test_step_residual_is_one_sort(self):
        cache = jnp.asarray(np.arange(0, 64, 2), jnp.int32)
        tok = jnp.asarray(np.random.default_rng(0).integers(0, 64, 48),
                          jnp.int32)
        jaxpr = jax.make_jaxpr(
            lambda c, t: step_residual(c, t, 16))(cache, tok)
        assert _count_sorts(jaxpr.jaxpr) == 1

    def test_residual_fed_segment_matches_fresh_sort(self):
        rng = np.random.default_rng(3)
        cache = jnp.asarray(np.sort(rng.choice(128, 16, replace=False)),
                            jnp.int32)
        tok = jnp.asarray(rng.integers(0, 128, 50), jnp.int32)
        g = jnp.asarray(rng.normal(size=(50, 8)), jnp.float32)
        res = step_residual(cache, tok, 16)
        ids_a, g_a = ops.segment_rows(tok, g, n_slots=50, pad_id=128)
        ids_b, g_b = ops.segment_rows(tok, g, n_slots=50, pad_id=128,
                                      residual=res.sort)
        np.testing.assert_array_equal(np.asarray(ids_a), np.asarray(ids_b))
        np.testing.assert_allclose(np.asarray(g_a), np.asarray(g_b))
        np.testing.assert_array_equal(
            np.asarray(ops.unique_rows(tok, n_slots=50, pad_id=128)),
            np.asarray(ops.unique_rows(tok, n_slots=50, pad_id=128,
                                       residual=res.sort)))

    def test_residual_probe_matches_probe_and_compact(self):
        rng = np.random.default_rng(5)
        cache = jnp.asarray(np.sort(rng.choice(256, 16, replace=False)),
                            jnp.int32)
        tok = jnp.asarray(rng.integers(0, 256, 37), jnp.int32)
        res = step_residual(cache, tok, 8)
        pc = probe_and_compact(cache, tok, 8)
        for a, b in zip(res.probe, pc):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# odd-shape sweep: n not a multiple of any block_r candidate, feature dims
# off the 128-lane grid (padded inside the kernels, sliced back out)
ODD_SHAPES = [
    # (V, D, n, block_r)
    (64, 128, 8, 4),
    (97, 190, 13, 4),
    (256, 576, 31, 8),
    (33, 570, 5, 3),
    (128, 64, 7, 16),
]


class TestMultiRowTiles:
    @pytest.mark.parametrize("V,D,n,block_r", ODD_SHAPES)
    def test_gather_matches_ref(self, V, D, n, block_r):
        rng = np.random.default_rng(0)
        table = jnp.asarray(rng.normal(size=(V, D)), jnp.float32)
        ids = jnp.asarray(rng.integers(0, V, size=(n,)), jnp.int32)
        out = embed_gather(table, ids, block_r=block_r, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(ref.embed_gather_ref(table, ids)))

    def test_gather_clamps_out_of_range_ids(self):
        """Id buffers padded with V (the serving runtime's residual and
        staging buffers) must not DMA past the table — on the chip that
        halts the core.  They read the clamped row, as XLA's gather."""
        V, D = 64, 128
        table = jnp.asarray(np.random.default_rng(4).normal(size=(V, D)),
                            jnp.float32)
        ids = jnp.asarray([3, V, V + 7, -1], jnp.int32)
        out = embed_gather(table, ids, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(table)[[3, V - 1, V - 1, 0]])

    @pytest.mark.parametrize("V,D,n,block_r", ODD_SHAPES)
    def test_scatter_matches_ref(self, V, D, n, block_r):
        rng = np.random.default_rng(1)
        base = jnp.zeros((V, D), jnp.float32)
        ids = jnp.asarray(rng.choice(V, size=(n,), replace=False),
                          jnp.int32)
        rows = jnp.asarray(rng.normal(size=(n, D)), jnp.float32)
        out = scatter_rows(base, ids, rows, block_r=block_r,
                           interpret=True)
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(ref.scatter_rows_ref(base, ids,
                                                             rows)))

    @pytest.mark.parametrize("V,D,n,block_r", ODD_SHAPES)
    def test_adagrad_matches_ref(self, V, D, n, block_r):
        rng = np.random.default_rng(2)
        table = jnp.asarray(rng.normal(size=(V, D)), jnp.float32)
        accum = jnp.asarray(rng.uniform(0.01, 1.0, size=(V, D)),
                            jnp.float32)
        ids = jnp.asarray(rng.choice(V, size=(n,), replace=False),
                          jnp.int32)
        grads = jnp.asarray(rng.normal(size=(n, D)), jnp.float32)
        new_t, new_a = adagrad_row_update(table, accum, ids, grads,
                                          lr=0.05, block_r=block_r,
                                          interpret=True)
        exp_t, exp_a = ref.adagrad_row_update_ref(table, accum, ids, grads,
                                                  lr=0.05)
        np.testing.assert_allclose(np.asarray(new_t), np.asarray(exp_t),
                                   rtol=2e-6, atol=2e-6)
        np.testing.assert_allclose(np.asarray(new_a), np.asarray(exp_a),
                                   rtol=2e-6, atol=2e-6)
        # untouched rows bit-identical (in-place aliasing semantics)
        mask = np.ones(V, bool)
        mask[np.asarray(ids)] = False
        np.testing.assert_array_equal(np.asarray(new_t)[mask],
                                      np.asarray(table)[mask])

    @pytest.mark.parametrize("V,D,n,block_r", ODD_SHAPES)
    def test_combine_matches_ref(self, V, D, n, block_r):
        rng = np.random.default_rng(3)
        C, M, T = 8, 4, max(3, n)
        cache_rows = jnp.asarray(rng.normal(size=(C, D)), jnp.float32)
        buf_rows = jnp.asarray(rng.normal(size=(M + 1, D)), jnp.float32)
        hit = jnp.asarray(rng.integers(0, 2, size=(T,)).astype(bool))
        cs = jnp.asarray(rng.integers(0, C, size=(T,)), jnp.int32)
        bs = jnp.asarray(rng.integers(0, M + 1, size=(T,)), jnp.int32)
        out = pm_combine(hit, cs, bs, cache_rows, buf_rows,
                         block_r=block_r, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(out),
            np.asarray(ref.pm_combine_ref(hit, cs, bs, cache_rows,
                                          buf_rows)))


class TestShardKernelMatrix:
    """Managed lookup fwd+bwd across kernel on/off × emulated shard
    counts {1, 2, 8} (no multi-device host needed: the EmulatedBackend is
    the single-host collective cost model)."""

    V, D, C = 256, 96, 16    # D off the lane grid on purpose

    def _setup(self, seed=0):
        rng = np.random.default_rng(seed)
        table = jnp.asarray(rng.normal(size=(self.V, self.D)), jnp.float32)
        cache_ids = jnp.asarray(
            np.sort(rng.choice(self.V, size=self.C, replace=False)),
            jnp.int32)
        return make_state(table, cache_ids), rng

    @pytest.mark.parametrize("n", [1, 2, 8])
    @pytest.mark.parametrize("kernel", [False, True])
    def test_fwd_bwd_matches_plain(self, n, kernel):
        st, rng = self._setup()
        be = EmulatedBackend(n)
        tokens = jnp.asarray(rng.integers(0, self.V, size=(2, 12)),
                             jnp.int32)
        out = pm_lookup(st.table, st.cache_ids, st.cache_rows, tokens, 24,
                        False, kernel, be)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(plain_lookup(st.table, tokens)),
            rtol=1e-6)

        def loss(t):
            return jnp.sum(pm_lookup(t, st.cache_ids, st.cache_rows,
                                     tokens, 24, False, kernel, be) ** 2)

        g = jax.grad(loss)(st.table)
        g_ref = jax.grad(
            lambda t: jnp.sum(plain_lookup(t, tokens) ** 2))(st.table)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                                   rtol=1e-5, atol=1e-6)


class TestBlockAutotuner:
    def test_pads_up_never_shrinks(self):
        # old rule: 576 -> 288, 570 -> 2.  Padding keeps full-lane tiles.
        assert blocking.pad_d(576) == 640
        assert blocking.pick_block_d(576, 512) == 128
        assert blocking.pick_block_d(570, 512) == 128
        assert blocking.pick_block_d(512, 512) == 512
        assert blocking.pick_block_d(1024, 512) == 512
        assert blocking.pick_block_d(64, 512) == 128

    def test_override_precedence(self):
        blocking.set_block_override(block_r=16, block_d=256)
        try:
            br, bd = blocking.pick_blocks("t", 64, 512, "f32")
            assert (br, bd) == (16, 256)
            # explicit args beat the override
            br, bd = blocking.pick_blocks("t", 64, 512, "f32", block_r=32)
            assert br == 32
        finally:
            blocking.set_block_override()

    def test_measured_path_caches_per_key(self, monkeypatch):
        monkeypatch.setenv("REPRO_AUTOTUNE", "measure")
        blocking.clear_autotune_cache()
        calls = []

        def bench(br, bd):
            calls.append((br, bd))
            return {8: 5.0, 16: 1.0, 32: 3.0}[br]

        br, bd = blocking.pick_blocks("bench-test", 64, 256, "f32",
                                      bench=bench)
        assert br == 16 and bd == 256
        n_calls = len(calls)
        assert n_calls >= 2            # it really measured candidates
        br2, _ = blocking.pick_blocks("bench-test", 64, 256, "f32",
                                      bench=bench)
        assert br2 == 16 and len(calls) == n_calls   # second hit cached
        blocking.clear_autotune_cache()

    def test_cache_key_includes_table_rows(self, monkeypatch):
        """ISSUE 6 regression: the same (kind, n, d) measured against the
        full table and a shard-local V/n block must NOT share a cached
        tile — inside `shard_map` the DMA probe pattern spreads over a
        different row count, so `table_rows` is part of the key."""
        monkeypatch.setenv("REPRO_AUTOTUNE", "measure")
        blocking.clear_autotune_cache()
        calls = []

        def bench_full(br, bd):
            calls.append(("full", br))
            return {8: 5.0, 16: 1.0, 32: 3.0}[br]

        def bench_shard(br, bd):
            calls.append(("shard", br))
            return {8: 5.0, 16: 3.0, 32: 1.0}[br]

        br_full, _ = blocking.pick_blocks("rows-test", 64, 256, "f32",
                                          table_rows=1024,
                                          bench=bench_full)
        br_shard, _ = blocking.pick_blocks("rows-test", 64, 256, "f32",
                                           table_rows=128,
                                           bench=bench_shard)
        assert br_full == 16 and br_shard == 32  # measured independently
        n_calls = len(calls)
        assert blocking.pick_blocks("rows-test", 64, 256, "f32",
                                    table_rows=1024,
                                    bench=bench_full)[0] == 16
        assert blocking.pick_blocks("rows-test", 64, 256, "f32",
                                    table_rows=128,
                                    bench=bench_shard)[0] == 32
        assert len(calls) == n_calls            # both served from cache
        blocking.clear_autotune_cache()

    def test_heuristic_when_off(self, monkeypatch):
        monkeypatch.setenv("REPRO_AUTOTUNE", "off")
        blocking.clear_autotune_cache()

        def bench(br, bd):              # must never be called
            raise AssertionError("measured in off mode")

        br, bd = blocking.pick_blocks("off-test", 64, 512, "f32",
                                      bench=bench)
        assert br == blocking.DEFAULT_BLOCK_R and bd == 512
        blocking.clear_autotune_cache()

    @pytest.mark.parametrize("n", [1, 5, 8, 13, 31, 64, 8192])
    @pytest.mark.parametrize("block_r", [None, 1, 2, 3, 4, 12, 16, 100])
    def test_never_returns_a_block_r_the_lowering_refuses(self, n,
                                                          block_r):
        """A row block must be a multiple of 8 or all n rows (the Pallas
        TPU lowering's (8, 128) rule), whatever the caller, override or
        measurement asked for."""
        blocking.clear_autotune_cache()
        for mode in ("off", "measure"):
            with pytest.MonkeyPatch.context() as mp:
                mp.setenv("REPRO_AUTOTUNE", mode)
                br, _ = blocking.pick_blocks(
                    f"legal-{mode}", n, 256, "f32", block_r=block_r,
                    bench=lambda r, d: 1.0 / r)
            assert br == n or (br % 8 == 0 and br < n), (mode, br)
        blocking.clear_autotune_cache()

    def test_never_measures_under_jit_tracing(self, monkeypatch):
        """Inside `jax.jit` the operands are tracers: timing a probe there
        measures tracing, not the kernel.  The wrappers fall back to the
        heuristic and report it as such."""
        import repro.kernels.embed_gather as gather_mod
        from repro.obs.telemetry import default_bus
        monkeypatch.setenv("REPRO_AUTOTUNE", "measure")
        blocking.clear_autotune_cache()
        timed = []
        monkeypatch.setattr(gather_mod, "time_bench",
                            lambda fn, iters=3: timed.append(fn) or 1.0)
        seen = len(default_bus().events("autotune.blocks"))
        table = jnp.zeros((96, 128), jnp.float32)
        ids = jnp.arange(40, dtype=jnp.int32) % 96
        out = jax.jit(lambda t, i: embed_gather(t, i, interpret=True))(
            table, ids)
        assert out.shape == (40, 128)
        assert timed == []
        events = default_bus().events("autotune.blocks")[seen:]
        assert [e["source"] for e in events] == ["heuristic"]
        blocking.clear_autotune_cache()
