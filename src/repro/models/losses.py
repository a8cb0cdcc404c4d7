"""Vocab-parallel cross-entropy with an explicit collective schedule.

Motivation (EXPERIMENTS.md §Perf, iterations 2-3): with the LM head sharded
over the vocab ("model") axis and tokens sharded over "data", GSPMD's
backward for ``dhead = h^T @ dlogits`` chooses to ALL-GATHER the f32
dlogits over the data axis (67 GB/device for nemotron-4-15b train_4k)
rather than computing token-partial (D, V/shard) products and all-reducing
them (0.8 GB).  This module writes the head matmul + CE loss inside
`shard_map`, so the collective schedule is explicit and the bad choice is
structurally impossible:

  forward per shard:  logits_blk = h_blk @ head_blk          (local MXU)
                      m   = pmax (model)  of row max          (B,S) tiny
                      lse = log(psum(model) sum exp) + m      (B,S) tiny
                      ll  = psum(model) masked label pick     (B,S) tiny
                      loss = psum(data+model) partial mean    scalar
  backward (autodiff of the above): dlogits stays shard-local; the head
  cotangent is a token-partial matmul + psum over "data" (inserted by
  shard_map's transpose rule for the data-replicated head input).
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@partial(jax.custom_jvp, nondiff_argnums=(1,))
def _pmax_stopgrad(x, axis_name):
    """pmax used purely as the logsumexp stability offset: mathematically
    the offset cancels, so a zero tangent is exact (and pmax has no
    built-in differentiation rule anyway)."""
    return jax.lax.pmax(x, axis_name)


@_pmax_stopgrad.defjvp
def _pmax_stopgrad_jvp(axis_name, primals, tangents):
    (x,) = primals
    return _pmax_stopgrad(x, axis_name), jnp.zeros_like(x)


def vocab_parallel_ce(h, head, labels, mesh, *, batch_axes: Tuple[str, ...],
                      model_axis: str = "model", aux=0.0,
                      aux_weight: float = 0.01):
    """Mean CE over tokens; h (B,S,D) batch-sharded, head (D,V)
    vocab-sharded, labels (B,S) batch-sharded."""
    V = head.shape[-1]
    msize = mesh.shape[model_axis]
    assert V % msize == 0, (V, msize)
    v_shard = V // msize

    def fn(h_blk, head_blk, labels_blk):
        # local logits: (b, s, V/msize)
        lg = (h_blk @ head_blk).astype(jnp.float32)
        idx = jax.lax.axis_index(model_axis)
        lo = idx * v_shard
        # stable logsumexp across the vocab-sharded axis
        m_loc = jnp.max(lg, axis=-1)
        m = _pmax_stopgrad(jax.lax.stop_gradient(m_loc), model_axis)
        se = jnp.sum(jnp.exp(lg - m[..., None]), axis=-1)
        lse = jnp.log(jax.lax.psum(se, model_axis)) + m
        # label pick: only the owning shard contributes
        local_label = labels_blk - lo
        in_shard = (local_label >= 0) & (local_label < v_shard)
        safe = jnp.clip(local_label, 0, v_shard - 1)
        pick = jnp.take_along_axis(lg, safe[..., None], axis=-1)[..., 0]
        ll = jax.lax.psum(jnp.where(in_shard, pick, 0.0), model_axis)
        # mean over the *global* token count
        n_local = lg.shape[0] * lg.shape[1]
        total = jnp.sum(lse - ll)
        total = jax.lax.psum(total, batch_axes)
        n = n_local * jax.lax.psum(jnp.ones((), jnp.float32), batch_axes)
        return total / n

    loss = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(batch_axes, None, None), P(None, model_axis),
                  P(batch_axes, None)),
        out_specs=P(),
    )(h, head, labels)
    return loss + aux_weight * aux
