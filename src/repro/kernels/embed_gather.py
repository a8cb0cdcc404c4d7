"""Pallas TPU kernel: blocked sparse row gather from an embedding table.

This is the read hot spot the paper's parameter manager serves (embedding /
KGE / CTR rows).  TPU adaptation: instead of per-key RPCs, the gather is a
scalar-prefetched blocked copy — the row ids live in SMEM (scalar
prefetch), the table stays in HBM (``memory_space=HBM``), and each grid
program fills its ``(block_r, block_d)`` output tile row by row.  Each row
is one guarded async DMA of the HBM tile that holds it (`kernels.rowdma`:
Mosaic moves whole (8, 128) tiles only), double-buffered over two VMEM
tile buffers and two DMA semaphores so row r+1's fetch is in flight while
row r is picked out of its tile.  Multi-row tiling shrinks the grid
~block_r× versus a one-row-per-program layout; the MXU is not involved;
the kernel is bandwidth-bound by design, and block_d is a multiple of the
128-lane VREG width — non-aligned feature dims are padded up, never tiled
down (`kernels.blocking`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .blocking import (measurable, pick_blocks, probe_operand, row_group,
                       tile_pad, time_bench)
from .rowdma import at_row, tile_copy


def _gather_kernel(ids_ref, table_ref, out_ref, buf, sem):
    # double-buffered row prefetch: the tile copy for row r+1 is started
    # before the wait on row r, so the next row's HBM fetch overlaps the
    # current row's completion.  Copies alternate over two tile buffers
    # and two semaphores; start and wait pair up by rebuilding the same
    # descriptor.
    i, j = pl.program_id(0), pl.program_id(1)
    block_r, block_d = out_ref.shape
    group = buf.shape[1]
    n = ids_ref.shape[0]
    col = pl.ds(j * block_d, block_d)

    def copy(r):
        return tile_copy(table_ref, ids_ref[i * block_r + r], group, col,
                         buf.at[r % 2], sem.at[r % 2])

    @pl.when(i * block_r < n)
    def _():
        copy(0).start()

    for r in range(block_r):
        row = i * block_r + r
        if r + 1 < block_r:
            @pl.when(row + 1 < n)
            def _():
                copy(r + 1).start()

        @pl.when(row < n)
        def _():
            copy(r).wait()

            def pick(s, r=r):
                out_ref[pl.ds(r, 1), :] = buf[r % 2, pl.ds(s, 1), :]

            at_row(ids_ref[row] % group, group, pick)


@functools.partial(jax.jit,
                   static_argnames=("block_r", "block_d", "interpret"))
def _embed_gather(table, ids, block_r: int, block_d: int, interpret: bool):
    n = ids.shape[0]
    V, D = table.shape
    # a DMA past the table halts the chip: out-of-range ids (the serving
    # runtime pads id buffers with V) read the nearest row, as XLA's
    # gather clamps
    ids = jnp.clip(ids.astype(jnp.int32), 0, V - 1)
    group = row_group(table.dtype)
    table = tile_pad(table, group)
    dp = table.shape[1]
    grid = (-(-n // block_r), dp // block_d)
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)],
            out_specs=pl.BlockSpec((block_r, block_d),
                                   lambda i, j, ids_ref: (i, j)),
            scratch_shapes=[pltpu.VMEM((2, group, block_d), table.dtype),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=jax.ShapeDtypeStruct((n, dp), table.dtype),
        interpret=interpret,
    )(ids, table)
    return out if dp == D else out[:, :D]


def embed_gather(table: jnp.ndarray, ids: jnp.ndarray, *,
                 block_r: int | None = None, block_d: int | None = None,
                 interpret: bool = True) -> jnp.ndarray:
    """Gather ``table[ids]``: table (V, D), ids (n,) int32 -> (n, D).
    Ids outside [0, V) are clamped into it.

    Grid: (ceil(n / block_r), D' // block_d); program (i, j) fills the
    j-tile of ``block_r`` table rows of its output tile."""
    n = ids.shape[0]
    V, D = table.shape
    bench = None
    if measurable(table, ids):
        def bench(br, bd):
            t, z = probe_operand(n, V, D, table.dtype)
            return time_bench(lambda: _embed_gather(t, z, br, bd, interpret))

    br, bd = pick_blocks("gather", n, D, table.dtype, table_rows=V,
                         block_r=block_r, block_d=block_d, bench=bench)
    return _embed_gather(table, ids, block_r=br, block_d=bd,
                         interpret=interpret)
