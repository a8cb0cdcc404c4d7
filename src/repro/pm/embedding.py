"""Intent-managed embedding: the TPU-native mapping of AdaPM (DESIGN.md §3b).

The embedding table is vocab-sharded over the ``model`` mesh axis (the
"allocation": every row has one owner shard).  A per-device *replica cache*
holds the rows the planner decided to replicate (rows with concurrent
multi-shard intent — AdaPM's selective replication).  Lookups take two
paths:

  hit  : the row is in the replica cache -> pure local read, no collective;
  miss : the row is only on its owner shard -> the *unique* missed ids are
         deduplicated and compacted into a fixed-capacity buffer (capacity
         M is *known in advance from intent* — the planner's per-unique-id
         `intent_miss_bound` — bucketed to keep shapes static) and served
         by one masked-partial-sum all-reduce over (M, D) instead of the
         dense (B*S, D) all-reduce of plain vocab-parallel embedding.

Every lookup variant here — the training VJP (`pm_lookup`), the serving
read-only probe-on-device (`serve_lookup`) and probe-at-admission
(`planned_serve_lookup`) modes, and the unmanaged baselines — is a thin
wrapper over ONE shared data path (`combine_miss_buffer`), parameterized
by a collective backend (`pm.collectives`): `EmulatedBackend` materializes
the owner-masked partials on a single device (the barrier cost model),
`MeshBackend` runs the real `shard_map` psum over a multi-device mesh
(DESIGN.md §10).

``kernel=True`` runs the row data-path through the Pallas kernels
(DESIGN.md §3c): blocked miss-buffer gather + scalar-prefetched per-token
combine forward, compact row scatter backward.

Replica synchronization: gradients NEVER flow into the cache (replicas are
not independent parameters).  A custom VJP routes all row gradients to the
owner-sharded table (`backend.scatter_row_grads` — a psum_scatter on the
mesh); the cache is re-gathered from the table once per refresh round
(`refresh_cache`, the backend's grouped all-gather), which in the
synchronous SPMD mapping bounds replica staleness to one round —
refresh-after-update gives exact equivalence with an unmanaged embedding
(tested).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.kernels import ops
from repro.kernels.pm_forward import (StepResidual, cache_probe,
                                      host_compact, probe_and_compact,
                                      step_residual)
from repro.pm.collectives import resolve


class EmbedPMState(NamedTuple):
    """Device-side state of the intent-managed embedding."""

    table: jnp.ndarray       # (V, D), vocab-sharded over "model"
    cache_ids: jnp.ndarray   # (C,) int32, SORTED; padded with V (no match)
    cache_rows: jnp.ndarray  # (C, D), replicated


def make_state(table: jnp.ndarray, cache_ids: jnp.ndarray,
               backend=None) -> EmbedPMState:
    """Build state with a freshly synchronized cache.  ``cache_ids`` must be
    sorted ascending; pad slots use V (matches no token).  ``backend``
    picks the collective that gathers the hot rows (the mesh backend's
    grouped all-gather; emulated/None reads locally)."""
    cache_ids = cache_ids.astype(jnp.int32)
    cache_rows = resolve(backend).refresh_rows(table, cache_ids)
    return EmbedPMState(table, cache_ids, cache_rows)


def refresh_cache(state: EmbedPMState, cache_ids: jnp.ndarray | None = None,
                  backend=None) -> EmbedPMState:
    """Replica sync round: re-gather the hot rows from their owners (one
    grouped all-gather on the mesh backend).  Optionally installs a new
    plan's ids."""
    ids = state.cache_ids if cache_ids is None else cache_ids
    return make_state(state.table, ids, backend)


def combine_miss_buffer(backend, table, cache_rows, hit, cache_slot,
                        buf_ids, buf_slot, *, kernel: bool = False,
                        n_miss=None):
    """THE shared managed-lookup data path (all variants funnel here):
    move the compact unique-miss buffer through the backend's
    vocab-parallel collective, append the all-zero trash row (slot M —
    overflow tokens land there), and per-token combine: hits read the
    local replica cache, misses read the buffer.  Returns (T, D) rows.

    ``n_miss`` (the probe's unique-miss count) switches the mesh backend
    onto the destination-compacted routed gather (DESIGN.md §12): only
    each owner's run of the compact ids moves, instead of the full
    replicated buffer riding a psum; its per-owner block derives from
    the buffer's length (`route_block_cap`)."""
    be = resolve(backend)
    if getattr(be, "mesh_real", False) and n_miss is not None:
        buf_rows = be.gather_rows_routed(
            table, buf_ids, jnp.minimum(n_miss, buf_ids.shape[0]),
            kernel=kernel)
    else:
        buf_rows = be.gather_rows(table, buf_ids, kernel=kernel)
    buffer = jnp.concatenate(
        [buf_rows, jnp.zeros((1, table.shape[1]), buf_rows.dtype)])
    combine = functools.partial(ops.pm_combine, use_pallas=kernel)
    if kernel and getattr(be, "mesh_real", False):
        # XLA never partitions a Pallas kernel: on a mesh, every device
        # runs the combine over the replicated operands, as it would the
        # jnp select
        combine = jax.shard_map(combine, mesh=be.mesh, in_specs=P(),
                                out_specs=P(), check_vma=False)
    return combine(hit, cache_slot, buf_slot, cache_rows, buffer)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def pm_lookup(table, cache_ids, cache_rows, tokens, miss_capacity: int,
              strict: bool = False, kernel: bool = False, backend=None,
              residual: StepResidual | None = None):
    """Intent-managed embedding lookup (training mode, differentiable).

    table (V, D); cache_ids (C,) sorted; cache_rows (C, D); tokens (B, S).
    ``miss_capacity``: static bound on cache-miss tokens per call — the
    planner derives it exactly from intent (per *unique* id; misses are
    deduplicated before compaction to keep that bound exact) and picks a
    bucket; overflow misses are transparently correct (they fall back to a
    second pass guarded by a predicate) but cost an extra dense lookup, so
    the planner sizing them away is the perf story, not a correctness
    requirement.  ``kernel=True`` routes the row data-path through the
    Pallas kernels (`repro.kernels`: blocked miss-buffer gather + per-token
    combine forward, blocked row scatter backward); the default jnp path is
    the bitwise reference.  ``backend`` selects the collective substrate
    (`pm.collectives`; None = single-device emulated reference).

    ``residual``: a precomputed `pm_forward.step_residual` for these
    (cache_ids, tokens) — the single-sort step contract (DESIGN.md §11):
    the train step computes the residual once and the forward compaction,
    the backward pre-sum AND the fused optimizer all consume it.  Left
    None, the lookup derives it here (still one sort: the forward's
    residual is saved for the backward, which never re-sorts).
    """
    out, _ = _pm_lookup_fwd(table, cache_ids, cache_rows, tokens,
                            miss_capacity, strict, kernel, backend,
                            residual)
    return out


def _lookup_impl(table, cache_ids, cache_rows, tokens, miss_capacity,
                 strict=False, kernel=False, backend=None, residual=None):
    B, S = tokens.shape
    T = B * S
    M = min(miss_capacity, T)
    tok = tokens.reshape(T).astype(jnp.int32)
    # probe + dedup/compact: UNIQUE missed ids fill the M intent-planned
    # slots (duplicates share a slot, matching `intent_miss_bound`);
    # computed from the step's one sort, or reused from the caller's
    if residual is None:
        residual = step_residual(cache_ids, tok, M)
    pc = residual.probe
    out = combine_miss_buffer(backend, table, cache_rows, pc.hit,
                              pc.cache_slot, pc.buf_ids, pc.buf_slot,
                              kernel=kernel, n_miss=pc.n_miss)

    def with_overflow(o):
        dense = resolve(backend).gather_rows(table, tok)
        return jnp.where(pc.overflow[:, None], dense, o)

    if not strict:
        # rare overflow: correctness fallback via a direct (dense) gather
        # through the same collective backend.  ``strict=True`` (dry-run /
        # planner-guaranteed capacity) omits the branch entirely so no
        # conditional dense collective is lowered.
        out = jax.lax.cond(pc.n_miss > M, with_overflow, lambda o: o, out)
    return out.reshape(B, S, table.shape[1]), residual


def _pm_lookup_fwd(table, cache_ids, cache_rows, tokens, miss_capacity,
                   strict=False, kernel=False, backend=None, residual=None):
    out, residual = _lookup_impl(table, cache_ids, cache_rows, tokens,
                                 miss_capacity, strict, kernel, backend,
                                 residual)
    # the sort residual rides to the backward so the duplicate pre-sum
    # never re-sorts the tokens it already sorted in the forward
    return out, (tokens, table.shape, residual.sort)


def _pm_lookup_bwd(miss_capacity, strict, kernel, backend, res, g):
    tokens, (V, D), srt = res
    B, S = tokens.shape
    T = B * S
    tok = tokens.reshape(T).astype(jnp.int32)
    gt = g.reshape(T, D)
    # replica write-back: ALL row gradients go to the owner-sharded table
    # (on the mesh backend a psum_scatter routes each summed row to its
    # owner's block; emulated = the dense/kernel scatter reference).  The
    # kernel/mesh paths pre-sum duplicates into compact slots using the
    # forward's sort residual — zero additional sorts.
    be = resolve(backend)
    if kernel or be.mesh_real:
        seg_ids, seg_g = ops.segment_rows(tok, gt, n_slots=T, pad_id=V,
                                          residual=srt)
        grad_table = be.scatter_row_grads(seg_ids, seg_g.astype(gt.dtype),
                                          V, kernel=kernel, segmented=True)
    else:
        grad_table = be.scatter_row_grads(tok, gt, V, kernel=False)
    return (grad_table, None, None, None, None)


pm_lookup.defvjp(_pm_lookup_fwd, _pm_lookup_bwd)


def plain_lookup(table, tokens):
    """Unmanaged vocab-parallel lookup (static-partitioning baseline)."""
    return jnp.take(table, tokens.astype(jnp.int32), axis=0)


# ---------------------------------------------------------------- serving

class ServeLookupResult(NamedTuple):
    """Outputs of the serving-mode lookup (all static shapes)."""

    out: jnp.ndarray       # (B, K, D) rows; overflow slots are zeros and
    #                        MUST NOT be served (re-queue their requests)
    hit: jnp.ndarray       # (B, K) bool, served from the replica cache
    overflow: jnp.ndarray  # (B, K) bool, unique misses beyond capacity
    n_miss: jnp.ndarray    # () int32, unique missed ids this batch


def shard_partial_sum(table, ids, n_shards: int, *, kernel: bool = False):
    """Back-compat alias: the emulated vocab-parallel gather — see
    `pm.collectives.EmulatedBackend.gather_rows` for the cost-model
    semantics (one barrier-materialized owner-masked partial per shard)."""
    return resolve(None, n_shards).gather_rows(table, ids, kernel=kernel)


def plain_serve_lookup(table, tokens, *, n_shards: int = 1, backend=None):
    """Unmanaged serving baseline: every token's row moves through the
    vocab-parallel collective (the dense (T, D) partial-sum)."""
    B, K = tokens.shape
    tok = tokens.reshape(B * K)
    out = resolve(backend, n_shards).gather_rows(table, tok)
    return out.reshape(B, K, -1)


def serve_lookup(table, cache_ids, cache_rows, tokens, miss_capacity: int,
                 *, n_shards: int = 1, kernel: bool = False,
                 backend=None) -> ServeLookupResult:
    """Serving-mode managed lookup: read-only (no VJP, no optimizer), and
    it NEVER falls back to a dense gather silently — misses beyond the
    planned capacity come back as zeros with their ``overflow`` flag set,
    and the runtime re-queues those requests (the request is served late,
    never wrong).  Hits read the local replica cache (no collective);
    unique misses are compacted into the intent-sized buffer and only that
    (M+1, D) buffer moves through the backend's vocab-parallel collective.
    """
    B, K = tokens.shape
    T = B * K
    M = min(miss_capacity, T)
    D = table.shape[1]
    tok = tokens.reshape(T).astype(jnp.int32)
    pc = probe_and_compact(cache_ids, tok, M)
    out = combine_miss_buffer(resolve(backend, n_shards), table, cache_rows,
                              pc.hit, pc.cache_slot, pc.buf_ids,
                              pc.buf_slot, kernel=kernel, n_miss=pc.n_miss)
    # overflow tokens route to the trash row -> zeros; make that explicit
    # (a planned buf id of 0 must not leak row 0 into an overflow slot)
    out = jnp.where(pc.overflow[:, None], 0.0, out)
    return ServeLookupResult(out.reshape(B, K, D),
                             pc.hit.reshape(B, K),
                             pc.overflow.reshape(B, K),
                             pc.n_miss)


class HostProbe(NamedTuple):
    """Host-side index stage of the serving lookup (all numpy)."""

    hit: np.ndarray         # (T,) bool, token served by the replica cache
    cache_slot: np.ndarray  # (T,) int32 cache row (clipped; valid on hit)
    buf_ids: np.ndarray     # (M,) int32 unique missed ids asc (pad: 0)
    buf_slot: np.ndarray    # (T,) int32 buffer slot per token (M = trash)
    overflow: np.ndarray    # (T,) bool, unique misses beyond capacity
    n_miss: int             # unique missed ids (may exceed M)


def probe_host(cache_ids, tok, miss_capacity: int, *,
               owner_shards: int = 0, route_capacity: int = 0,
               vocab: int = 0) -> HostProbe:
    """Numpy mirror of `kernels.pm_forward.probe_and_compact` for the
    serving runtime's admission path.

    ``owner_shards`` / ``route_capacity`` / ``vocab`` (all three required
    to engage) additionally flag *per-owner* overflow for the mesh
    backend's routed miss path (DESIGN.md §12): a unique missed id whose
    rank within its owner shard (owner = id // (V / owner_shards); the
    compact ids are ascending, so ranks are positional) reaches
    ``route_capacity`` would not fit the routed per-destination block, and
    every token reading its slot gets its ``overflow`` flag set — the
    runtime re-queues those requests exactly like global-capacity
    overflow, so admission capacity matches the per-owner buffers the
    routed collective actually has.

    On the serving hot path the scheduler holds the batch's token ids on
    the host the moment the batch is formed (they came out of the request
    queue) — so the whole index stage (probe, dedup, compact, overflow
    flags) runs here in numpy at admission time, and the device executes
    pure data movement (`planned_serve_lookup`).  This is the same
    scalar-path/data-path split the Pallas kernels use (indices in SMEM
    via scalar prefetch, rows in VMEM), applied host-side; it also means
    miss-rate/overflow drift feedback needs no device readback at all.

    There are no parallel implementations to pin against each other
    anymore: this IS `pm_forward._compact_math` — the same arithmetic the
    device `step_residual`/`probe_and_compact` runs, executed on numpy
    (`pm_forward.host_compact`) — so host and device probes cannot drift
    (the pin test now checks one implementation against itself on two
    array backends)."""
    r = host_compact(cache_ids, tok, miss_capacity)
    overflow = r["overflow"]
    if owner_shards > 0 and route_capacity > 0 and vocab > 0:
        overflow = _route_overflow(r["hit"], r["buf_ids"], r["buf_slot"],
                                   overflow, int(r["n_miss"]),
                                   owner_shards, route_capacity, vocab)
    return HostProbe(r["hit"], r["cache_slot"], r["buf_ids"],
                     r["buf_slot"], overflow, int(r["n_miss"]))


def _route_overflow(hit, buf_ids, buf_slot, overflow, n_miss: int,
                    owner_shards: int, route_capacity: int,
                    vocab: int) -> np.ndarray:
    """Per-owner overflow flags for the routed miss path (DESIGN.md §12),
    shared by `probe_host` and `CacheProbeView`: a unique missed id whose
    rank within its owner shard reaches ``route_capacity`` would not fit
    the routed per-destination block.  The compact ids are ascending, so
    each owner's ids are one contiguous run and rank-within-owner is
    positional (the device router's layout)."""
    M = buf_ids.shape[0]
    nm = min(int(n_miss), M)
    ids = np.asarray(buf_ids[:nm], dtype=np.int64)
    block = -(-vocab // owner_shards)
    starts = np.searchsorted(ids, np.arange(owner_shards,
                                            dtype=np.int64) * block)
    rank = np.arange(nm) - starts[np.minimum(ids // block,
                                             owner_shards - 1)]
    slot_over = np.zeros(M + 1, dtype=bool)
    slot_over[:nm] = rank >= min(route_capacity, M)
    return overflow | (slot_over[buf_slot] & ~hit)


class CacheProbeView:
    """Host probe for ONE cache generation.

    `probe_host` re-derives the probe from scratch on every batch — one
    argsort of the batch tokens PLUS a binary search of every token
    against the sorted cache ids — and runs the full `_compact_math`
    index stage besides.  This view keeps only the sorted (C,) cache ids
    of the generation, so building it costs O(C) and allocates nothing
    V-sized; each batch then pays one binary search of its tokens
    against them and the `np.unique` over its missed tokens, which any
    compaction needs.  Every `HostProbe` field is byte-identical to
    `probe_host` (pinned in tests/test_prefetch.py) — the slot and hit
    come from `_compact_math`'s own `cache_probe`, and `np.unique`
    returns the missed ids ascending with duplicates sharing one inverse
    slot, exactly `_compact_math`'s miss-group ranks."""

    def __init__(self, cache_ids: np.ndarray, vocab: int):
        self.cache_ids = np.asarray(cache_ids)
        self.vocab = int(vocab)

    def probe(self, tok, miss_capacity: int, *, owner_shards: int = 0,
              route_capacity: int = 0) -> HostProbe:
        """`probe_host(self.cache_ids, tok, ...)`, by one binary search."""
        tok = np.asarray(tok, dtype=np.int32)
        T = tok.shape[0]
        M = miss_capacity
        cache_slot, hit = cache_probe(np, self.cache_ids, tok)
        miss = ~hit
        uniq, inverse = np.unique(tok[miss], return_inverse=True)
        n_miss = int(uniq.shape[0])
        k = min(n_miss, M)
        buf_ids = np.zeros(M, np.int32)
        buf_ids[:k] = uniq[:k]
        buf_slot = np.full(T, M, np.int32)
        buf_slot[miss] = np.where(inverse < M, inverse, M).astype(np.int32)
        overflow = np.zeros(T, bool)
        overflow[miss] = inverse >= M
        if owner_shards > 0 and route_capacity > 0 and self.vocab > 0:
            overflow = _route_overflow(hit, buf_ids, buf_slot, overflow,
                                       n_miss, owner_shards,
                                       route_capacity, self.vocab)
        return HostProbe(hit, cache_slot, buf_ids, buf_slot, overflow,
                         n_miss)


def planned_serve_lookup(table, cache_rows, buf_ids, hit, cache_slot,
                         buf_slot, *, n_shards: int = 1,
                         kernel: bool = False, backend=None,
                         n_miss=None):
    """Device data path of the serving lookup, with the index stage
    already done (`probe_host` at admission — intent means the host knows
    the batch's miss set before the batch runs).  Only the (M+1, D)
    compact buffer moves through the backend's vocab-parallel collective;
    hits read the local replica cache; overflow slots read the all-zero
    trash row (``buf_slot == M``) and their requests are re-queued by the
    runtime, never served.  Returns (T, D) rows.

    ``n_miss`` (host probe's unique-miss count, passed as a device
    scalar) routes the mesh backend onto the destination-compacted gather
    with per-owner blocks of `route_block_cap` (M, n_shards): one program
    per buffer shape.  A batch whose misses crowd one owner past that
    block takes the psum fallback arm, still exact (the runtime counts
    it as ``serve.route_fallbacks``)."""
    return combine_miss_buffer(resolve(backend, n_shards), table,
                               cache_rows, hit, cache_slot, buf_ids,
                               buf_slot, kernel=kernel, n_miss=n_miss)


# The staged serving dispatch needs no dedicated device fn: the runtime
# folds the tenure's staging buffer into the cache side (``cache_rows ++
# staging_rows``, one concat per tenure) and converts staged miss tokens
# into extended-cache hits at admission, so the device path is
# `planned_serve_lookup` over the residual bucket alone — no extra
# gathers or masks per round (DESIGN.md §15).
