"""Pallas TPU kernel: row-wise scatter of compact gradient rows into the
owner-sharded table gradient.

Backward of the managed lookup: duplicate token gradients are pre-summed
(`ops.segment_rows` fed by the step's sort residual — no extra sort), then
this kernel writes each aggregated row into its table slot.  The dense
(V, D) gradient is the donated zero buffer (``memory_space=HBM`` +
input/output aliasing, in-place on TPU) and only the touched row tiles
ever move: each grid program owns a ``(block_r, block_d)`` gradient tile
and, per row, reads the HBM tile holding the target row, replaces the row
in VMEM and writes the tile back (`kernels.rowdma`: Mosaic moves whole
(8, 128) tiles only).  Copies are waited in row order and the grid is
sequential, so a read always observes the preceding write, also when two
target rows share a tile.

Row ids must be unique; pad slots point at a caller-provided trash row
(the managed path uses row V of a (V+1, D) buffer, sliced off afterwards),
so colliding pad writes are harmless last-wins zeros.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .blocking import (measurable, pick_blocks, probe_operand, row_group,
                       tile_pad, time_bench)
from .rowdma import at_row, tile_copy, tile_store


def _scatter_kernel(ids_ref, base_ref, rows_ref, out_ref, buf, sem):
    i, j = pl.program_id(0), pl.program_id(1)
    block_r, block_d = rows_ref.shape
    group = buf.shape[0]
    n = ids_ref.shape[0]
    col = pl.ds(j * block_d, block_d)
    for r in range(block_r):
        row = i * block_r + r

        @pl.when(row < n)
        def _():
            idx = ids_ref[row]
            cin = tile_copy(out_ref, idx, group, col, buf, sem)
            cin.start()
            cin.wait()

            def put(s, r=r):
                buf[pl.ds(s, 1), :] = rows_ref[pl.ds(r, 1), :]

            at_row(idx % group, group, put)
            cout = tile_store(buf, out_ref, idx, group, col, sem)
            cout.start()
            cout.wait()


@functools.partial(jax.jit,
                   static_argnames=("block_r", "block_d", "interpret"))
def _scatter_rows(base, ids, rows, block_r: int, block_d: int,
                  interpret: bool):
    n = ids.shape[0]
    R, D = base.shape
    group = row_group(base.dtype)
    base = tile_pad(base, group)
    dp = base.shape[1]
    if dp != D:
        rows = jnp.pad(rows, ((0, 0), (0, dp - D)))
    HBM = pltpu.MemorySpace.HBM
    grid = (-(-n // block_r), dp // block_d)
    out = pl.pallas_call(
        _scatter_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec(memory_space=HBM),                      # base
                pl.BlockSpec((block_r, block_d),
                             lambda i, j, ids_ref: (i, j)),          # rows
            ],
            out_specs=pl.BlockSpec(memory_space=HBM),
            scratch_shapes=[pltpu.VMEM((group, block_d), base.dtype),
                            pltpu.SemaphoreType.DMA],
        ),
        out_shape=jax.ShapeDtypeStruct(base.shape, base.dtype),
        input_output_aliases={1: 0},
        interpret=interpret,
    )(ids.astype(jnp.int32), base, rows.astype(base.dtype))
    return out if out.shape == (R, D) else out[:R, :D]


def scatter_rows(base: jnp.ndarray, ids: jnp.ndarray, rows: jnp.ndarray, *,
                 block_r: int | None = None, block_d: int | None = None,
                 interpret: bool = True) -> jnp.ndarray:
    """out = base with out[ids[i]] = rows[i]; base (R, D) is donated
    (in-place on TPU), ids (n,) int32 unique row indices, rows (n, D)."""
    n = ids.shape[0]
    R, D = base.shape
    bench = None
    if measurable(base, ids, rows):
        def bench(br, bd):
            b, z = probe_operand(n, R, D, base.dtype)
            return time_bench(
                lambda: _scatter_rows(b, z, rows, br, bd, interpret))

    br, bd = pick_blocks("scatter", n, D, base.dtype, table_rows=R,
                         block_r=block_r, block_d=block_d, bench=bench)
    return _scatter_rows(base, ids, rows, block_r=br, block_d=bd,
                         interpret=interpret)
