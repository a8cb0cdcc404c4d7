"""Pallas TPU kernel: fused sparse AdaGrad row update (scatter-apply).

The paper trains all five tasks with AdaGrad (§C); the write hot spot of a
parameter manager is applying sparse row updates:

    accum[id] += g^2
    table[id] -= lr * g / (sqrt(accum[id]) + eps)

TPU adaptation: table and accumulator stay in HBM (``memory_space=HBM``)
and are donated in place (input/output aliasing — no fresh (V, D)
allocation per step).  Each grid program owns a ``(block_r, block_d)``
gradient tile and, per row: DMAs the HBM tiles of table and accumulator
that hold the row into VMEM (`kernels.rowdma`: Mosaic moves whole (8, 128)
tiles only), computes the update over the whole tile, selects it into
the target row only — the other rows are written back bitwise unchanged
— and DMAs both tiles back.  The copies are waited in row order inside the
program and the grid is sequential, so a read always observes the
preceding write (the property the pad-slot reversal in `train.steps`
relies on, and what keeps two target rows of one tile correct).

Row ids must be UNIQUE within one call (duplicates are pre-aggregated by
`repro.kernels.ops.segment_rows`, which itself reuses the step's sort
residual); duplicate ids would not race, but their sequential-apply
semantics would differ from the summed-gradient oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .blocking import (measurable, pick_blocks, probe_operand, row_group,
                       tile_pad, time_bench)
from .rowdma import tile_copy, tile_store


def _make_kernel(lr: float, eps: float):
    def kernel(ids_ref, table_ref, accum_ref, grad_ref,
               table_out, accum_out, tbuf, abuf, sem):
        i, j = pl.program_id(0), pl.program_id(1)
        block_r, block_d = grad_ref.shape
        group = tbuf.shape[0]
        n = ids_ref.shape[0]
        col = pl.ds(j * block_d, block_d)
        for r in range(block_r):
            row = i * block_r + r

            @pl.when(row < n)
            def _():
                idx = ids_ref[row]
                cin = [tile_copy(table_out, idx, group, col, tbuf, sem.at[0]),
                       tile_copy(accum_out, idx, group, col, abuf, sem.at[1])]
                for c in cin:
                    c.start()
                for c in cin:
                    c.wait()
                on_row = jax.lax.broadcasted_iota(
                    jnp.int32, (group, block_d), 0) == idx % group
                g = grad_ref[pl.ds(r, 1), :].astype(jnp.float32)
                a = abuf[...].astype(jnp.float32)
                t = tbuf[...].astype(jnp.float32)
                acc = a + g * g
                p = t - lr * g / (jnp.sqrt(acc) + eps)
                abuf[...] = jnp.where(on_row, acc, a).astype(abuf.dtype)
                tbuf[...] = jnp.where(on_row, p, t).astype(tbuf.dtype)
                cout = [tile_store(tbuf, table_out, idx, group, col,
                                   sem.at[0]),
                        tile_store(abuf, accum_out, idx, group, col,
                                   sem.at[1])]
                for c in cout:
                    c.start()
                for c in cout:
                    c.wait()
    return kernel


@functools.partial(jax.jit, static_argnames=("lr", "eps", "block_r",
                                             "block_d", "interpret"))
def _adagrad_row_update(table, accum, ids, grads, lr: float, eps: float,
                        block_r: int, block_d: int, interpret: bool):
    n = ids.shape[0]
    V, D = table.shape
    group = max(row_group(table.dtype), row_group(accum.dtype))
    table = tile_pad(table, group)
    accum = tile_pad(accum, group)
    dp = table.shape[1]
    if dp != D:
        grads = jnp.pad(grads, ((0, 0), (0, dp - D)))
    grid = (-(-n // block_r), dp // block_d)
    HBM = pltpu.MemorySpace.HBM
    out = pl.pallas_call(
        _make_kernel(float(lr), float(eps)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec(memory_space=HBM),                   # table
                pl.BlockSpec(memory_space=HBM),                   # accum
                pl.BlockSpec((block_r, block_d),
                             lambda i, j, ids_ref: (i, j)),       # grads
            ],
            out_specs=[pl.BlockSpec(memory_space=HBM),
                       pl.BlockSpec(memory_space=HBM)],
            scratch_shapes=[pltpu.VMEM((group, block_d), table.dtype),
                            pltpu.VMEM((group, block_d), accum.dtype),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=[jax.ShapeDtypeStruct(table.shape, table.dtype),
                   jax.ShapeDtypeStruct(accum.shape, accum.dtype)],
        input_output_aliases={1: 0, 2: 1},  # table->out0, accum->out1
        interpret=interpret,
    )(ids.astype(jnp.int32), table, accum, grads)
    if out[0].shape != (V, D):
        out = [o[:V, :D] for o in out]
    return tuple(out)


def adagrad_row_update(table: jnp.ndarray, accum: jnp.ndarray,
                       ids: jnp.ndarray, grads: jnp.ndarray, *,
                       lr: float = 0.1, eps: float = 1e-8,
                       block_r: int | None = None,
                       block_d: int | None = None,
                       interpret: bool = True):
    """Apply AdaGrad to rows ``ids`` of (table, accum) with ``grads``.

    table, accum: (V, D); ids: (n,) unique int32; grads: (n, D).
    Returns (new_table, new_accum); both alias their inputs (in-place on
    TPU: donated buffers, no fresh HBM allocation for the full tables).
    """
    n = ids.shape[0]
    V, D = table.shape
    bench = None
    if measurable(table, accum, ids, grads):
        def bench(br, bd):
            t, z = probe_operand(n, V, D, table.dtype)
            a = jnp.zeros(t.shape, accum.dtype)
            return time_bench(
                lambda: _adagrad_row_update(t, a, z, grads, lr, eps, br, bd,
                                            interpret))

    br, bd = pick_blocks("adagrad", n, D, table.dtype, table_rows=V,
                         block_r=block_r, block_d=block_d, bench=bench)
    return _adagrad_row_update(table, accum, ids, grads, lr=lr, eps=eps,
                               block_r=br, block_d=bd, interpret=interpret)
