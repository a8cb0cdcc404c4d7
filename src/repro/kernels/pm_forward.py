"""Pallas TPU kernels + index residuals: the fused intent-managed
embedding forward path.

The managed lookup (DESIGN.md §3c, §11) is a three-stage pipeline:

  probe   : binary-search every token against the sorted replica-cache ids;
  compact : deduplicate the missed ids and compact them into the planner's
            intent-sized buffer of M slots (per *unique* id — this is what
            makes `engine.intent_miss_bound` an exact bound);
  gather  : move the row data — the M unique missed rows come out of the
            owner-sharded table through the blocked `embed_gather` kernel,
            and the per-token select between cache row and miss-buffer row
            is the `pm_combine` kernel below.

Single-sort step residual (§11): the probe/compact stage used to be
re-derived by every consumer — the forward compaction, the backward
`segment_rows` pre-sum and the fused sparse-optimizer row dedup each ran
their own O(T log T) argsort over the same token ids.  `step_residual`
now computes everything a managed train/serve step needs from ONE argsort:

  * the ProbeCompact fields (hit flags, cache slots, unique-miss buffer);
  * the full-token sort permutation + per-token unique-group slot
    (`SortResidual`) that `ops.segment_rows` / `ops.unique_rows` consume
    instead of re-sorting.

The arithmetic lives in `_compact_math`, written once against a tiny
numpy/jnp shim so the device path and the serving runtime's host-side
admission probe (`pm.embedding.probe_host`) are literally the same code.

The row data-path — the part that is bandwidth-bound — never touches a
dense (T, D) table gather: hits read the replicated cache, misses read
the compact (M+1, D) buffer (slot M is the all-zeros overflow/trash row).
`pm_combine` moves it in (block_r, block_d) multi-row tiles: row indices
are scalar-prefetched into SMEM and each grid program issues one guarded
DMA per row, of the HBM tile that holds it (`kernels.rowdma`) — only the
*winning* source (cache or buffer) is staged into VMEM, half the bytes of
the old stage-both layout.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .blocking import pick_blocks, row_group, tile_pad
from .rowdma import at_row, tile_copy


class ProbeCompact(NamedTuple):
    """Index-stage outputs of the managed lookup (all static shapes)."""

    hit: jnp.ndarray         # (T,) bool, token served by the replica cache
    cache_slot: jnp.ndarray  # (T,) int32 cache row (clipped; valid on hit)
    buf_ids: jnp.ndarray     # (M,) int32 UNIQUE missed ids (pad: 0)
    buf_slot: jnp.ndarray    # (T,) int32 buffer slot per token (M = trash)
    n_miss: jnp.ndarray      # () int32 count of unique missed ids
    overflow: jnp.ndarray    # (T,) bool, unique misses beyond capacity M


class SortResidual(NamedTuple):
    """The reusable product of one token-id argsort: enough to aggregate
    duplicate rows (`ops.segment_rows`) or compact unique ids
    (`ops.unique_rows`) without sorting again."""

    order: jnp.ndarray       # (T,) int32 argsort permutation of the ids
    sorted_ids: jnp.ndarray  # (T,) int32 ids[order]
    slot: jnp.ndarray        # (T,) int32 unique-group index per sorted pos


class StepResidual(NamedTuple):
    """Everything a managed step derives from its token ids, computed from
    a single argsort: the probe/compact index stage (forward) plus the
    full-token sort residual (backward pre-sum + sparse optimizer)."""

    probe: ProbeCompact
    sort: SortResidual
    n_uniq: jnp.ndarray      # () int32 unique token ids in the step


def _jnp_scatter_set(dst, idx, val):
    return dst.at[idx].set(val)


def _np_scatter_set(dst, idx, val):
    dst[idx] = val
    return dst


def cache_probe(xp, cache_ids, tok):
    """Each token's slot in the sorted (C,) cache ids and whether it is
    cached there: one binary search, for numpy and jnp alike."""
    T = tok.shape[0]
    C = cache_ids.shape[0]
    if not C:
        return xp.zeros((T,), xp.int32), xp.zeros((T,), bool)
    cache_slot = xp.clip(xp.searchsorted(cache_ids, tok),
                         0, C - 1).astype(xp.int32)
    return cache_slot, cache_ids[cache_slot] == tok


def _compact_math(xp, scatter_set, cache_ids, tok, miss_capacity: int):
    """THE probe/compact/segment arithmetic, once, for numpy and jnp.

    One argsort of the raw token ids orders every duplicate group; hits
    are identified independently by binary search, so the same sorted
    view yields (a) the unique *missed* ids in ascending order — each
    claims one dense buffer slot, duplicates share it, overflow beyond
    ``miss_capacity`` routes to the trash slot M — and (b) the unique-id
    compaction over ALL tokens that the backward/optimizer reuse.

    Deduplication is load-bearing: the planner's `intent_miss_bound`
    counts unique ids per step, so duplicate missed tokens must share one
    slot for the static capacity to be exact (see ISSUE 2)."""
    M = miss_capacity
    T = tok.shape[0]
    int32 = xp.int32
    cache_slot, hit = cache_probe(xp, cache_ids, tok)

    order = xp.argsort(tok).astype(int32)        # THE step's one sort
    s = tok[order]
    hs = hit[order]
    first = xp.concatenate([xp.ones((1,), bool), s[1:] != s[:-1]])
    # unique-id compaction over all tokens (backward/optimizer residual)
    seg_slot = (xp.cumsum(first.astype(int32)) - 1).astype(int32)
    n_uniq = xp.sum(first.astype(int32))
    # unique MISSED ids claim dense buffer slots in ascending-id order
    # (hit status is constant within a duplicate group)
    miss_first = first & ~hs
    mgrp = (xp.cumsum(miss_first.astype(int32)) - 1).astype(int32)
    n_miss = xp.sum(miss_first.astype(int32))
    in_buf = miss_first & (mgrp < M)
    buf_ids = scatter_set(xp.zeros((M + 1,), int32),
                          xp.where(in_buf, mgrp, M),
                          xp.where(in_buf, s, 0).astype(int32))[:M]
    slot_sorted = xp.where(~hs & (mgrp < M), mgrp, M).astype(int32)
    buf_slot = scatter_set(xp.zeros((T,), int32), order, slot_sorted)
    over_sorted = ~hs & (mgrp >= M)
    overflow = scatter_set(xp.zeros((T,), bool), order, over_sorted)
    return dict(hit=hit, cache_slot=cache_slot, buf_ids=buf_ids,
                buf_slot=buf_slot, n_miss=n_miss, overflow=overflow,
                order=order, sorted_ids=s.astype(int32), seg_slot=seg_slot,
                n_uniq=n_uniq)


@functools.partial(jax.jit, static_argnames=("miss_capacity",))
def step_residual(cache_ids: jnp.ndarray, tok: jnp.ndarray,
                  miss_capacity: int) -> StepResidual:
    """Probe (T,) tokens against the sorted cache and derive the FULL step
    residual — probe/compact index stage plus the reusable sort — from a
    single argsort.  Compute once per managed step; every other consumer
    (backward pre-sum, sparse row optimizer, kernel scalar prefetch) reads
    these arrays instead of re-sorting."""
    r = _compact_math(jnp, _jnp_scatter_set, cache_ids,
                      tok.astype(jnp.int32), miss_capacity)
    return StepResidual(
        probe=ProbeCompact(r["hit"], r["cache_slot"], r["buf_ids"],
                           r["buf_slot"], r["n_miss"], r["overflow"]),
        sort=SortResidual(r["order"], r["sorted_ids"], r["seg_slot"]),
        n_uniq=r["n_uniq"])


def probe_and_compact(cache_ids: jnp.ndarray, tok: jnp.ndarray,
                      miss_capacity: int) -> ProbeCompact:
    """Index-stage-only view of `step_residual` (serving probes and other
    callers that do not need the backward/optimizer sort residual)."""
    return step_residual(cache_ids, tok, miss_capacity).probe


def host_compact(cache_ids: np.ndarray, tok: np.ndarray,
                 miss_capacity: int) -> dict:
    """Numpy twin of `step_residual` for host-side admission probes — the
    SAME `_compact_math`, so device and host can never drift apart."""
    return _compact_math(np, _np_scatter_set, np.asarray(cache_ids),
                         np.asarray(tok, dtype=np.int32), miss_capacity)


# ------------------------------------------------------------- pm_combine

def _combine_kernel(hit_ref, cslot_ref, bslot_ref, cache_ref, buf_ref,
                    out_ref, tile, sem):
    # multi-row tile: one guarded tile DMA per row, and only the WINNING
    # source (cache on hit, miss buffer otherwise) ever moves into VMEM
    i, j = pl.program_id(0), pl.program_id(1)
    block_r, block_d = out_ref.shape
    group = tile.shape[0]
    T = hit_ref.shape[0]
    col = pl.ds(j * block_d, block_d)
    for r in range(block_r):
        row = i * block_r + r

        def fetch(src_ref, idx, r=r):
            cp = tile_copy(src_ref, idx, group, col, tile, sem)
            cp.start()
            cp.wait()

            def pick(s):
                out_ref[pl.ds(r, 1), :] = tile[pl.ds(s, 1), :]

            at_row(idx % group, group, pick)

        @pl.when(row < T)
        def _():
            hit = hit_ref[row] != 0
            pl.when(hit)(lambda: fetch(cache_ref, cslot_ref[row]))
            pl.when(jnp.logical_not(hit))(
                lambda: fetch(buf_ref, bslot_ref[row]))


@functools.partial(jax.jit,
                   static_argnames=("block_r", "block_d", "interpret"))
def _pm_combine(hit, cache_slot, buf_slot, cache_rows, buf_rows,
                block_r: int, block_d: int, interpret: bool):
    T = hit.shape[0]
    D = cache_rows.shape[1]
    group = row_group(cache_rows.dtype)
    cache_rows = tile_pad(cache_rows, group)
    buf_rows = tile_pad(buf_rows, group)
    dp = cache_rows.shape[1]
    grid = (-(-T // block_r), dp // block_d)
    HBM = pltpu.MemorySpace.HBM
    out = pl.pallas_call(
        _combine_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[pl.BlockSpec(memory_space=HBM),
                      pl.BlockSpec(memory_space=HBM)],
            out_specs=pl.BlockSpec((block_r, block_d),
                                   lambda i, j, h, s, p: (i, j)),
            scratch_shapes=[pltpu.VMEM((group, block_d), cache_rows.dtype),
                            pltpu.SemaphoreType.DMA],
        ),
        out_shape=jax.ShapeDtypeStruct((T, dp), cache_rows.dtype),
        interpret=interpret,
    )(hit.astype(jnp.int32), cache_slot.astype(jnp.int32),
      buf_slot.astype(jnp.int32), cache_rows, buf_rows)
    return out if dp == D else out[:, :D]


def pm_combine(hit: jnp.ndarray, cache_slot: jnp.ndarray,
               buf_slot: jnp.ndarray, cache_rows: jnp.ndarray,
               buf_rows: jnp.ndarray, *, block_r: int | None = None,
               block_d: int | None = None,
               interpret: bool = True) -> jnp.ndarray:
    """Per-token select: out[i] = cache_rows[cache_slot[i]] on hit else
    buf_rows[buf_slot[i]].  cache_rows (C, D); buf_rows (M+1, D) with the
    trash row last; returns (T, D).  Tiled (block_r, block_d); the feature
    dim is lane-padded, never shrunk (`kernels.blocking`).

    The three (T,) index vectors are scalar-prefetched into SMEM, 12
    bytes per token: a v5e core's 1 MiB of SMEM holds T up to ~87k."""
    br, bd = pick_blocks("pm_combine", hit.shape[0], cache_rows.shape[1],
                         cache_rows.dtype, table_rows=cache_rows.shape[0],
                         block_r=block_r, block_d=block_d)
    return _pm_combine(hit, cache_slot, buf_slot, cache_rows, buf_rows,
                       block_r=br, block_d=bd, interpret=interpret)
