"""Compile-only checks of the row kernels for TPU v5e chips.

Interpret mode (every other kernel test) runs the kernel bodies in Python
and cannot see what the chip's compiler refuses: unaligned row slices of
a tiled HBM array, block shapes off the (8, 128) grid, scalar-prefetch
vectors beyond SMEM.  Here each kernel's private entry point is lowered
with ``interpret=False`` for a described (not attached) v5e chip and
compiled by the installed TPU compiler, at the widths the smoke run uses:
the smollm-135m table (49152 x 576) and one step of batch 8 x seq 1024
tokens.  Nothing runs, so results and times are out of scope.

The topology is described inside a module fixture, never at import: the
TPU library may be loaded by one process at a time, and only the worker
that runs this file loads it.  The persistent compilation cache is off in
this file, since executables compiled for a described chip cannot be read
back without one.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.kernels import blocking, ops
from repro.kernels.adagrad_rows import _adagrad_row_update
from repro.kernels.embed_gather import _embed_gather
from repro.kernels.pm_forward import _pm_combine
from repro.kernels.scatter_rows import _scatter_rows

V, D = 49152, 576        # smollm-135m vocabulary x width
T = 8 * 1024             # tokens per step: batch 8 x seq 1024
M = 8192                 # miss-buffer capacity at that step
C = V // 8               # largest replica cache the controller picks
DTYPES = [pytest.param(jnp.float32, id="f32"),
          pytest.param(jnp.bfloat16, id="bf16")]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            from jax.experimental import topologies
            try:
                topo = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:  # no TPU compiler, or no topology
                pytest.skip(f"no v5e:2x2 topology can be described: {e}")
            yield topo
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _blocks(kind, n, rows, dtype):
    return blocking.pick_blocks(kind, n, D, dtype, table_rows=rows)


def _compiled(fn, *specs):
    compiled = fn.lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("dtype", DTYPES)
def test_embed_gather_compiles(one_chip, dtype):
    br, bd = _blocks("gather", M, V, dtype)
    _compiled(jax.jit(lambda t, i: _embed_gather(t, i, br, bd, False)),
              _spec((V, D), dtype, one_chip),
              _spec((M,), jnp.int32, one_chip))


@pytest.mark.parametrize("dtype", DTYPES)
def test_scatter_rows_compiles(one_chip, dtype):
    # the managed backward scatters into a (V + 1, D) buffer: row V is
    # the trash row of the pad slots
    br, bd = _blocks("scatter", T, V + 1, dtype)
    _compiled(jax.jit(lambda b, i, r: _scatter_rows(b, i, r, br, bd,
                                                    False)),
              _spec((V + 1, D), dtype, one_chip),
              _spec((T,), jnp.int32, one_chip),
              _spec((T, D), dtype, one_chip))


@pytest.mark.parametrize("dtype", DTYPES)
def test_adagrad_rows_compiles(one_chip, dtype):
    br, bd = _blocks("adagrad", T, V, dtype)
    _compiled(jax.jit(lambda t, a, i, g: _adagrad_row_update(
        t, a, i, g, 0.01, 1e-8, br, bd, False)),
        _spec((V, D), dtype, one_chip), _spec((V, D), dtype, one_chip),
        _spec((T,), jnp.int32, one_chip), _spec((T, D), dtype, one_chip))


@pytest.mark.parametrize("dtype", DTYPES)
def test_pm_combine_compiles(one_chip, dtype):
    # three (T,) int32 vectors are scalar-prefetched into SMEM
    br, bd = _blocks("pm_combine", T, C, dtype)
    _compiled(jax.jit(lambda h, cs, bs, cr, br_: _pm_combine(
        h, cs, bs, cr, br_, br, bd, False)),
        *[_spec((T,), jnp.int32, one_chip)] * 3,
        _spec((C, D), dtype, one_chip), _spec((M + 1, D), dtype, one_chip))


@pytest.mark.parametrize("kind", ["scatter", "adagrad"])
def test_row_writers_update_donated_table_in_place(one_chip, kind):
    """At a lane-aligned width the writers alias the donated (V, D)
    buffers and the compiled program holds no table-sized temporary: no
    relayout or copy of the table or the accumulator per call."""
    Dl = 512
    table = _spec((V, Dl), jnp.float32, one_chip)
    ids = _spec((T,), jnp.int32, one_chip)
    rows = _spec((T, Dl), jnp.float32, one_chip)
    br, bd = blocking.pick_blocks(kind, T, Dl, jnp.float32, table_rows=V)
    if kind == "scatter":
        fn = jax.jit(lambda b, i, r: _scatter_rows(b, i, r, br, bd, False),
                     donate_argnums=0)
        compiled = _compiled(fn, table, ids, rows)
        donated = V * Dl * 4
    else:
        fn = jax.jit(lambda t, a, i, g: _adagrad_row_update(
            t, a, i, g, 0.01, 1e-8, br, bd, False), donate_argnums=(0, 1))
        compiled = _compiled(fn, table, table, ids, rows)
        donated = 2 * V * Dl * 4
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == donated
    assert mem.temp_size_in_bytes < V * Dl * 4 // 8


def test_managed_lookup_compiles_on_a_four_chip_mesh(topo, monkeypatch):
    """XLA cannot partition a Pallas kernel: on the vocab-sharded mesh
    every kernel of the managed lookup must sit inside a shard_map.  The
    serving data path (routed miss gather + combine) compiles for four
    chips with the kernels native."""
    from repro.pm.collectives import MeshBackend
    from repro.pm.embedding import planned_serve_lookup
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    mesh = Mesh(np.asarray(topo.devices[:4]), ("model",))
    rows = NamedSharding(mesh, P("model", None))
    rep = NamedSharding(mesh, P())
    backend = MeshBackend(mesh)
    fn = jax.jit(lambda t, cr, bi, h, cs, bs, nm: planned_serve_lookup(
        t, cr, bi, h, cs, bs, kernel=True, backend=backend, n_miss=nm))
    compiled = _compiled(
        fn, _spec((V, D), jnp.float32, rows),
        _spec((C, D), jnp.float32, rep), _spec((M,), jnp.int32, rep),
        *[_spec((T,), jnp.int32, rep)] * 3,
        _spec((), jnp.int32, rep))
    assert compiled.as_text().count("tpu_custom_call") >= 2
