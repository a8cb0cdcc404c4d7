"""Collective backends: the vocab-parallel communication layer of the
intent-managed embedding (DESIGN.md §10).

The managed lookup's perf claim is about what moves through the network:
only the compact ``(M+1, D)`` miss buffer instead of every token's row.
This module isolates *how* that movement happens behind a small backend
protocol so the lookup data path (`pm.embedding`) is written once and the
collective substrate is swappable:

  `EmulatedBackend`
      The single-device reference.  ``n_shards > 1`` materializes one
      owner-masked ``(n, D)`` partial per shard behind
      `lax.optimization_barrier` — the cost model that stands in for the
      all-reduce's wire bytes on a one-device host (the seed repo's
      ``shard_partial_sum``).  ``n_shards == 1`` degenerates to a plain
      (optionally Pallas-blocked) gather, which is the training default.

  `MeshBackend`
      The real thing: the table is sharded ``P(axis, None)`` over a JAX
      device mesh and every data movement is an explicit `shard_map`
      collective.  Since this PR the hot path is *destination-compacted
      routing* (DESIGN.md §12) — the ascending unique-id layout that falls
      out of the step's one sort already groups ids by owner shard, so
      per-owner blocks are carved with `searchsorted` + `dynamic_slice`
      (no extra sort) and each device touches only the rows it owns:

        gather_rows_routed  each owner gathers its contiguous run of the
                          compact miss ids from its local ``(V/n, D)``
                          block into a fixed ``(cap, D)`` send block; one
                          `lax.all_gather` of the per-owner blocks
                          reassembles the replicated ``(M, D)`` buffer —
                          per-device comm ~ ``n * cap * D = O(M·D)``,
                          independent of n_shards (vs the replicated
                          psum's ``O(M·D·n)``).  A skewed batch whose
                          per-owner count exceeds the static cap falls
                          back to the masked psum under one `lax.cond`;
        gather_rows       the legacy replicated path (masked partial
                          gather per shard + `lax.psum` of the full
                          buffer) — the routed path's fallback arm and the
                          benchmark baseline;
        scatter_row_grads segment slots are chunked over shards; each
                          shard destination-compacts its chunk (ascending
                          -> contiguous per-owner runs) and one
                          `lax.all_to_all` hands every owner exactly its
                          rows, which scatter-add into the local
                          ``(V/n, D)`` block — the dense ``(V, D)``
                          partial + tiled psum_scatter of the legacy path
                          (kept as `scatter_row_grads_psum`) never
                          materialize;
        update_rows       the fused sparse AdaGrad applied where the row
                          lives: the same all_to_all routing delivers
                          (id, grad-row) pairs to their owners and the
                          row kernel updates the owner's local block
                          in-place inside the same shard_map;
        refresh_rows      replica sync via the routed gather over the
                          sorted hot-id set (pad ids ``>= V`` belong to
                          no shard and come back zero).

      Runs on any multi-device backend; CI exercises it on CPU via
      ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

Backends are frozen dataclasses (hashable) so they ride through
`jax.custom_vjp` nondiff args and `jax.jit` static closures without
recompilation churn.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.kernels import ops, ref


def route_block_cap(m: int, n: int) -> int:
    """Static per-owner block size of the routed miss path: the expected
    even split ``ceil(m / n)`` with 2x headroom for skew, rounded to a
    power of two (few distinct caps -> few compiled variants), never above
    ``m`` itself.  Batches whose worst per-owner count exceeds this fall
    back to the replicated psum under `lax.cond` — the same
    correctness-over-capacity contract as the miss buffer's overflow
    branch."""
    c = 2 * (-(-m // n))
    p = 1
    while p < c:
        p *= 2
    return min(m, p)


def route_falls_back(ids, vocab: int, n: int) -> bool:
    """Host mirror of the `lax.cond` in `MeshBackend.gather_rows_routed`
    at its derived block: True when the real ids (``< vocab``) of the
    host buffer ``ids`` crowd one of the ``n`` owners past
    `route_block_cap(len(ids), n)`, so the gather takes the psum arm."""
    ids = np.asarray(ids)
    cap = route_block_cap(ids.shape[0], n)
    if cap >= ids.shape[0]:
        return False
    owner = ids[ids < vocab].astype(np.int64) // (vocab // n)
    return owner.size > cap and \
        int(np.bincount(owner, minlength=n).max()) > cap


def _all_to_all_route(axis: str, n: int, block: int, vocab: int,
                      tokp, gp, cap: int):
    """INSIDE-shard_map half of the routed scatter/update: destination-
    compact this shard's ``cap``-slot chunk of the (padded, ascending)
    segment slots and exchange per-owner blocks with one `lax.all_to_all`.

    The chunk is a contiguous slice of a globally ascending unique-id
    list, so each destination's rows form one contiguous run —
    `searchsorted` finds the run starts and ``rank = j - start[owner]``
    places each row in its send block; a run can never exceed the chunk
    length ``cap``, so the send layout ``(n * cap,)`` needs no overflow
    arm.  Pad slots (id == vocab) are dropped on send and arrive as
    sentinel ids on the receive side.  Returns ``(recv_ids, recv_g)``:
    ``n * cap`` global ids (vocab = pad) with their gradient rows, all
    owned by this shard."""
    k = jax.lax.axis_index(axis)
    tc = jax.lax.dynamic_slice_in_dim(tokp, k * cap, cap)
    gc = jax.lax.dynamic_slice_in_dim(gp, k * cap, cap, axis=0)
    starts = jnp.searchsorted(
        tc, jnp.arange(n, dtype=jnp.int32) * block).astype(jnp.int32)
    j = jnp.arange(cap, dtype=jnp.int32)
    owner = tc // block
    valid = tc < vocab
    rank = j - starts[jnp.clip(owner, 0, n - 1)]
    dst = jnp.where(valid, owner * cap + rank, n * cap)
    send_ids = jnp.full((n * cap,), vocab, jnp.int32).at[dst].set(
        tc, mode="drop")
    send_g = jnp.zeros((n * cap, gp.shape[1]), gp.dtype).at[dst].set(
        gc, mode="drop")
    recv_ids = jax.lax.all_to_all(send_ids, axis, 0, 0, tiled=True)
    recv_g = jax.lax.all_to_all(send_g, axis, 0, 0, tiled=True)
    return recv_ids, recv_g


@dataclass(frozen=True)
class EmulatedBackend:
    """Single-host stand-in for the vocab-parallel collectives.

    With ``n_shards > 1`` each gather materializes one owner-masked
    ``(n, D)`` partial per shard behind `lax.optimization_barrier` so XLA
    cannot algebraically fuse the mask-and-sum back into a plain gather:
    every shard's message is a real ``(n, D)`` buffer, the cost model for
    its wire bytes (proportional to ``n_shards * len(ids) * D`` — exactly
    the lever the managed path pulls by routing only the compact miss
    buffer through it)."""

    n_shards: int = 1
    mesh_real: bool = field(default=False, init=False)

    def gather_rows(self, table, ids, *, kernel: bool = False):
        """Rows for ``ids`` through the emulated collective."""
        ids = ids.astype(jnp.int32)
        rows = ops.embed_gather(table, ids, use_pallas=kernel) if kernel \
            else jnp.take(table, ids, axis=0)
        if self.n_shards <= 1:
            return rows
        V = table.shape[0]
        block = -(-V // self.n_shards)
        owner = ids // block
        partial = jnp.zeros_like(rows)
        for s in range(self.n_shards):
            msg = jnp.where((owner == s)[:, None], rows, 0.0)
            partial = partial + jax.lax.optimization_barrier(msg)
        return partial

    def scatter_row_grads(self, tok, g, vocab_size: int, *,
                          kernel: bool = False, segmented: bool = False):
        """Route all row gradients to the (conceptually owner-sharded)
        table: dense scatter-add, or — ``kernel`` — compact unique slots
        followed by one blocked Pallas scatter (pad slots hit the sentinel
        trash row V).  ``segmented`` marks (tok, g) as ALREADY
        duplicate-pre-summed compact slots (the lookup backward feeds the
        forward's sort residual through `ops.segment_rows`), so no index
        work happens here."""
        V = vocab_size
        if not kernel:
            # pad/sentinel ids (== V, only present on segmented inputs)
            # fall outside the table and are dropped
            return jnp.zeros((V, g.shape[1]),
                             dtype=g.dtype).at[tok].add(g, mode="drop")
        if segmented:
            slot_ids, slot_g = tok, g
        else:
            slot_ids, slot_g = ops.segment_rows(tok, g,
                                                n_slots=tok.shape[0],
                                                pad_id=V)
        base = jnp.zeros((V + 1, g.shape[1]), dtype=g.dtype)
        return ops.scatter_rows(base, slot_ids, slot_g)[:V]

    def refresh_rows(self, table, cache_ids):
        """Replica sync: gather the hot rows (pad ids >= V read zeros).
        Eager-friendly op-by-op — the XLA CPU backend lowers a jitted
        clip+gather+mask into a far slower fused gather."""
        V = table.shape[0]
        ids = cache_ids.astype(jnp.int32)
        return ops.masked_embed_gather(table, jnp.clip(ids, 0, V - 1),
                                       ids < V, use_pallas=False)

    def refresh_rows_delta(self, table, cache_rows, ids, slots):
        """Incremental replica sync: re-gather only ``ids`` (ascending,
        V-padded) and write them into ``cache_rows`` at ``slots`` (pad
        slots == C fall off the end and are dropped).  Rows the optimizer
        did not touch since the last refresh are bitwise unchanged in the
        table, so skipping them is exact — the delta-refresh gate in
        `train/loop.py` only takes this path when that holds (sparse
        AdaGrad, untied embeddings)."""
        V = table.shape[0]
        ids = ids.astype(jnp.int32)
        rows = ops.masked_embed_gather(table, jnp.clip(ids, 0, V - 1),
                                       ids < V, use_pallas=False)
        return cache_rows.at[slots.astype(jnp.int32)].set(rows, mode="drop")

    def update_rows(self, table, accum, seg_ids, seg_g, *, lr: float,
                    eps: float = 1e-8, kernel: bool = False):
        """Fused sparse AdaGrad over segment slots: ``seg_ids`` are the
        ascending unique batch ids followed by sentinel (== V) pads with
        zero gradients (`ops.segment_rows` output).  Single-device
        reference of the mesh backend's on-shard routed update — the
        training step calls this through the backend so the optimizer
        applies where the row lives on every substrate.

        The slot order is REVERSED for the kernel path so every pad
        program (an identity write: zero grad, original row value) runs
        before row 0's real update — the grid executes in order, so the
        real update always lands last and a trailing pad can never
        overwrite it with the stale row.  The jnp path uses the
        scatter-ADD form, which is order-free under zero-grad
        duplicates."""
        V = table.shape[0]
        ids = seg_ids[::-1]
        valid = ids < V
        ids = jnp.where(valid, ids, 0)
        rows_g = seg_g[::-1] * valid[:, None].astype(seg_g.dtype)
        if kernel:
            return ops.adagrad_row_update(table, accum, ids, rows_g,
                                          lr=lr, eps=eps)
        return ref.adagrad_row_add_ref(table, accum, ids, rows_g,
                                       lr=lr, eps=eps)


@dataclass(frozen=True)
class MeshBackend:
    """Real SPMD collectives over a device mesh: the table lives sharded
    ``P(axis, None)`` (contiguous vocab blocks, shard k owns rows
    ``[k*V/n, (k+1)*V/n)``) and `shard_map` makes every transfer an
    explicit psum / psum_scatter.  Requires ``V % n_shards == 0`` (the
    same divisibility `models.losses.vocab_parallel_ce` asserts).

    ``check_vma=False`` on the shard_maps: the Pallas gather kernel has no
    replication rule, and the outputs' replication is structural (psum ->
    replicated, psum_scatter -> sharded by construction)."""

    mesh: jax.sharding.Mesh
    axis: str = "model"
    mesh_real: bool = field(default=True, init=False)

    @property
    def n_shards(self) -> int:
        return self.mesh.shape[self.axis]

    def place_table(self, table):
        """Owner-shard the table over the mesh (the §3b allocation) via
        `launch.sharding.managed_table_sharding`."""
        from repro.launch.sharding import managed_table_sharding
        return jax.device_put(table,
                              managed_table_sharding(self.mesh, self.axis))

    def _check(self, V: int) -> int:
        n = self.n_shards
        if V % n:
            raise ValueError(
                f"vocab {V} must divide the {self.axis!r} axis ({n})")
        return V // n

    def gather_rows(self, table, ids, *, kernel: bool = False):
        """Masked partial gather per shard + psum of the compact buffer:
        each shard gathers the rows it owns (zeros elsewhere) from its
        local ``(V/n, D)`` block — Pallas-blocked when ``kernel`` — and
        one `lax.psum` moves the summed ``(n, D)`` buffer to every shard.
        Ids outside every block (e.g. cache pad V) come back zero."""
        V = table.shape[0]
        block = self._check(V)

        def f(tblk, ids):
            lo = jax.lax.axis_index(self.axis) * block
            local = ids.astype(jnp.int32) - lo
            inb = (local >= 0) & (local < block)
            rows = ops.masked_embed_gather(
                tblk, jnp.clip(local, 0, block - 1), inb, use_pallas=kernel)
            return jax.lax.psum(rows, self.axis)

        return jax.shard_map(
            f, mesh=self.mesh,
            in_specs=(P(self.axis, None), P(None)), out_specs=P(None),
            check_vma=False)(table, ids)

    def gather_rows_routed(self, table, ids, n_valid, *,
                           route_cap: int = 0, kernel: bool = False):
        """Destination-compacted miss gather (DESIGN.md §12): ``ids`` must
        be ascending unique real ids on ``ids[:n_valid]`` (the
        probe/compact contract — unique missed ids claim buffer slots in
        ascending-id order, so the step's one sort already grouped them by
        owner); pad entries after may hold anything and come back ZERO
        (unlike `gather_rows`, which returns row 0 for pad id 0 — callers
        never read pad slots either way).

        Each owner carves its contiguous run out of the id list
        (`ops.owner_segments`: searchsorted + dynamic_slice, no sort),
        gathers those rows from its local ``(V/n, D)`` block into a fixed
        ``(cap, D)`` send block tagged with the original buffer slots, and
        one `lax.all_gather` of the per-owner blocks reassembles the
        replicated ``(M, D)`` buffer — every consumer needs every row (the
        activations are replicated over the model axis), so the all-to-all
        degenerates into an all-gather of owner blocks, and each row
        crosses the wire once per consumer instead of riding all n psum
        partials: per-device comm ``n * cap * D ~ 2·M·D``, independent of
        n_shards.

        ``route_cap`` pins the static per-owner block; 0 (every caller in
        the program) derives `route_block_cap(M, n)` from the buffer's
        length, so the block adds no shape of its own.  A batch whose
        worst per-owner count exceeds the cap falls back to the
        replicated psum under one `lax.cond` — correct, just slower
        (`route_falls_back` decides the same on the host)."""
        V, D = table.shape
        block = self._check(V)
        n = self.n_shards
        M = ids.shape[0]
        cap = min(M, route_cap) if route_cap > 0 else route_block_cap(M, n)
        view, seg = ops.owner_segments(ids, n_valid, n, block)
        viewp = jnp.concatenate([view, jnp.full((cap,), V, jnp.int32)])

        def routed(_):
            def f(tblk, viewp, seg):
                k = jax.lax.axis_index(self.axis)
                start = seg[k]
                cnt = seg[k + 1] - start
                sl = jax.lax.dynamic_slice_in_dim(viewp, start, cap)
                j = jnp.arange(cap, dtype=jnp.int32)
                mine = j < cnt
                local = jnp.clip(sl - k * block, 0, block - 1)
                rows = ops.masked_embed_gather(tblk, local, mine,
                                               use_pallas=kernel)
                # original buffer slot of each sent row; padding lands on
                # the extra slot M and is sliced off after reassembly
                slots = jnp.where(mine, start + j, M)
                rows_all = jax.lax.all_gather(rows, self.axis)
                slots_all = jax.lax.all_gather(slots, self.axis)
                buf = jnp.zeros((M + 1, D), rows.dtype)
                buf = buf.at[slots_all.reshape(-1)].add(
                    rows_all.reshape(-1, D))
                return buf[:M]

            return jax.shard_map(
                f, mesh=self.mesh,
                in_specs=(P(self.axis, None), P(None), P(None)),
                out_specs=P(None), check_vma=False)(table, viewp, seg)

        if cap >= M:        # the cap cannot be exceeded: no fallback arm
            return routed(None)
        counts = seg[1:] - seg[:-1]
        return jax.lax.cond(jnp.max(counts) <= cap, routed,
                            lambda _: self.gather_rows(table, view,
                                                       kernel=kernel),
                            None)

    def scatter_row_grads(self, tok, g, vocab_size: int, *,
                          kernel: bool = False, segmented: bool = False):
        """all_to_all-routed row gradients: segment slots are chunked over
        the mesh axis, each shard destination-compacts its chunk (the
        global slot list is ascending unique ids then V-pads, so a chunk's
        per-owner rows are contiguous runs — `_all_to_all_route`) and one
        `lax.all_to_all` hands every owner exactly its rows, which
        scatter-add into the local ``(V/n, D)`` block.  Neither the dense
        ``(V, D)`` partial nor the tiled psum_scatter of the legacy path
        (`scatter_row_grads_psum`) is materialized: per-device wire is the
        ``(n·cap, D)`` send/recv blocks, ~``T·D / n`` each way.

        Non-``segmented`` inputs are segmented here first (one sort, off
        the single-sort hot path — every in-repo mesh caller arrives
        segmented through the lookup backward's residual-fed pass)."""
        V = vocab_size
        n = self.n_shards
        block = self._check(V)
        if not segmented:
            seg_ids, seg_g = ops.segment_rows(tok, g, n_slots=tok.shape[0],
                                              pad_id=V)
            tok, g = seg_ids, seg_g.astype(g.dtype)
        D = g.shape[1]
        T = tok.shape[0]
        cap = -(-T // n)
        pad = n * cap - T
        tokp = jnp.concatenate(
            [tok.astype(jnp.int32), jnp.full((pad,), V, jnp.int32)])
        gp = jnp.concatenate([g, jnp.zeros((pad, D), g.dtype)])

        def f(tokp, gp):
            recv_ids, recv_g = _all_to_all_route(self.axis, n, block, V,
                                                 tokp, gp, cap)
            k = jax.lax.axis_index(self.axis)
            local = recv_ids - k * block
            ok = (local >= 0) & (local < block)
            return jnp.zeros((block, D), gp.dtype).at[
                jnp.where(ok, local, block)].add(recv_g, mode="drop")

        return jax.shard_map(
            f, mesh=self.mesh, in_specs=(P(None), P(None)),
            out_specs=P(self.axis, None), check_vma=False)(tokp, gp)

    def scatter_row_grads_psum(self, tok, g, vocab_size: int, *,
                               kernel: bool = False,
                               segmented: bool = False):
        """Legacy replicated-partial path (the PR-4 data movement, kept as
        the routed path's benchmark/equivalence baseline): each shard
        scatter-adds its chunk into a local dense ``(V, D)`` partial and
        one tiled `lax.psum_scatter` both sums the partials and delivers
        each owner its ``(V/n, D)`` block."""
        V = vocab_size
        n = self.n_shards
        self._check(V)
        D = g.shape[1]
        T = tok.shape[0]
        cap = -(-T // n)
        pad = n * cap - T
        tokp = jnp.concatenate(
            [tok.astype(jnp.int32), jnp.full((pad,), V, jnp.int32)])
        gp = jnp.concatenate([g, jnp.zeros((pad, D), g.dtype)])

        def f(tokp, gp):
            i = jax.lax.axis_index(self.axis)
            tc = jax.lax.dynamic_slice_in_dim(tokp, i * cap, cap)
            gc = jax.lax.dynamic_slice_in_dim(gp, i * cap, cap, axis=0)
            if kernel and not segmented:
                tc, gc = ops.segment_rows(tc, gc, n_slots=cap, pad_id=V)
                gc = gc.astype(gp.dtype)
            partial = jnp.zeros((V, D), gp.dtype).at[tc].add(gc,
                                                             mode="drop")
            return jax.lax.psum_scatter(partial, self.axis,
                                        scatter_dimension=0, tiled=True)

        return jax.shard_map(
            f, mesh=self.mesh, in_specs=(P(None), P(None)),
            out_specs=P(self.axis, None), check_vma=False)(tokp, gp)

    def update_rows(self, table, accum, seg_ids, seg_g, *, lr: float,
                    eps: float = 1e-8, kernel: bool = False):
        """The on-shard fused sparse optimizer: the same all_to_all
        routing as `scatter_row_grads` delivers each (id, grad-row) pair
        to its owner, and the fused AdaGrad row kernel updates the owner's
        local ``(V/n, D)`` table/accumulator blocks inside the same
        shard_map — no dense sweep, no dense gradient, no second
        collective.  ``seg_ids`` / ``seg_g`` follow the `segment_rows`
        contract (ascending unique ids, then V-pads with zero gradients).

        Received pad slots alias local row 0 with a zero gradient — safe
        on the kernel path because the sequential grid re-reads the row
        before each (identity) write, and on the jnp path because the
        scatter-ADD form is order-free under zero-grad duplicates; real
        received ids are unique per shard (chunks are disjoint slices of
        a globally unique list)."""
        V, D = table.shape
        block = self._check(V)
        n = self.n_shards
        T = seg_ids.shape[0]
        cap = -(-T // n)
        pad = n * cap - T
        tokp = jnp.concatenate(
            [seg_ids.astype(jnp.int32), jnp.full((pad,), V, jnp.int32)])
        gp = jnp.concatenate([seg_g, jnp.zeros((pad, D), seg_g.dtype)])

        def f(tblk, ablk, tokp, gp):
            recv_ids, recv_g = _all_to_all_route(self.axis, n, block, V,
                                                 tokp, gp, cap)
            k = jax.lax.axis_index(self.axis)
            local = recv_ids - k * block
            ok = (local >= 0) & (local < block)
            ids_l = jnp.where(ok, local, 0)
            g_l = recv_g * ok[:, None].astype(recv_g.dtype)
            if kernel:
                return ops.adagrad_row_update(tblk, ablk, ids_l[::-1],
                                              g_l[::-1], lr=lr, eps=eps)
            return ref.adagrad_row_add_ref(tblk, ablk, ids_l, g_l,
                                           lr=lr, eps=eps)

        return jax.shard_map(
            f, mesh=self.mesh,
            in_specs=(P(self.axis, None), P(self.axis, None), P(None),
                      P(None)),
            out_specs=(P(self.axis, None), P(self.axis, None)),
            check_vma=False)(table, accum, tokp, gp)

    def refresh_rows(self, table, cache_ids):
        """Replica sync round: the grouped all-gather of the plan's hot
        rows through the routed gather — ``cache_ids`` are sorted
        ascending with V-pads (the cache contract), exactly the layout the
        router wants, and `searchsorted` recovers the real-id count
        without a sort.  Pad ids >= V belong to no shard and come back
        zero — the padded-cache contract.  Jitted (`_routed_refresh`): an
        eager call would trace and compile its `shard_map` and `lax.cond`
        anew on every refresh."""
        return _routed_refresh(self, table, cache_ids)

    def refresh_rows_delta(self, table, cache_rows, ids, slots):
        """Incremental replica sync through the routed owner-block
        gather: only ``ids`` (ascending, V-padded — the layout the router
        wants) cross the mesh; everything else in ``cache_rows`` is
        bitwise current already (delta-refresh gate, `train/loop.py`).
        Pad slots == C drop off the end of the cache buffer."""
        rows = _routed_refresh(self, table, ids)
        return cache_rows.at[slots.astype(jnp.int32)].set(rows, mode="drop")


@functools.partial(jax.jit, static_argnums=0)
def _routed_refresh(backend: MeshBackend, table, ids):
    """`MeshBackend.gather_rows_routed` over ascending V-padded ``ids``,
    compiled once per backend and shape."""
    ids = ids.astype(jnp.int32)
    n_valid = jnp.searchsorted(ids, jnp.int32(table.shape[0]))
    return backend.gather_rows_routed(table, ids, n_valid)


#: module-level default: the training path's single-device reference.
EMULATED = EmulatedBackend(1)


def resolve(backend, n_shards: int = 1):
    """``backend`` if given, else the emulated backend at ``n_shards`` —
    the rule every `pm.embedding` entry point applies to its arguments."""
    if backend is not None:
        return backend
    return EMULATED if n_shards <= 1 else EmulatedBackend(n_shards)


def make_backend(collective: str, model_shards: int = 0):
    """Config-string entry point shared by the training loop and the
    serving runtime: ``"emulated"`` -> None (the per-call `resolve`
    default), ``"mesh"`` -> a `MeshBackend` over the first
    ``model_shards`` local devices (0 = all, `launch.mesh.
    make_model_mesh`).  Callers owning a table should `place_table` it."""
    if collective == "emulated":
        return None
    if collective == "mesh":
        from repro.launch.mesh import make_model_mesh
        return MeshBackend(make_model_mesh(model_shards))
    raise ValueError(f"unknown collective {collective!r}")
