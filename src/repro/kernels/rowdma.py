"""Row access into tiled HBM arrays, shared by the row kernels.

Mosaic moves a 2-D HBM array only in whole (8, 128) tiles of 32-bit words
(`blocking.row_group` rows per tile): a DMA of one row out of a (V, D)
table is refused at compile time ("slice shape along dimension 0 must be
aligned to tiling").  So a kernel that wants row ``idx`` copies the
``group`` rows of the tile that holds it into a VMEM buffer and picks the
row there; a kernel that writes row ``idx`` reads the tile, replaces the
row in VMEM and writes the tile back.  Operands must be padded to whole
tiles (`blocking.tile_pad`) so every tile slice is in bounds.

Within VMEM a packed dtype (bf16) can be indexed along sublanes only at
static offsets, so `at_row` branches once per row of the group.
"""

from __future__ import annotations

import functools

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def group_base(idx, group: int):
    """First row of the tile holding row ``idx`` (tile-aligned)."""
    return pl.multiple_of(idx // group * group, group)


def tile_copy(hbm_ref, idx, group: int, col, vmem_ref, sem):
    """DMA descriptor between the tile holding row ``idx`` (columns
    ``col``) of ``hbm_ref`` and the ``(group, block_d)`` VMEM buffer."""
    return pltpu.make_async_copy(
        hbm_ref.at[pl.ds(group_base(idx, group), group), col], vmem_ref, sem)


def tile_store(vmem_ref, hbm_ref, idx, group: int, col, sem):
    """DMA descriptor writing the VMEM buffer back over the tile holding
    row ``idx`` of ``hbm_ref``."""
    return pltpu.make_async_copy(
        vmem_ref, hbm_ref.at[pl.ds(group_base(idx, group), group), col], sem)


def at_row(off, group: int, body) -> None:
    """Run ``body(s)`` for the one static row ``s == off`` of a group."""
    for s in range(group):
        pl.when(off == s)(functools.partial(body, s))
