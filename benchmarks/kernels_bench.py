"""Kernel micro-benchmarks: interpret-mode Pallas correctness timing plus
the pure-jnp oracle (the CPU-speed reference; real perf is a TPU property,
see §Roofline for the bandwidth-bound analysis).

Also benches the intent-managed embedding hot path end to end (forward +
backward + row update) against the unmanaged `plain_lookup` baseline across
Zipf skews: the managed path probes the replica cache, compacts the
*unique* misses into the intent-sized buffer, and applies the optimizer to
exactly the touched rows — the plain path pays a dense (V, D) gradient
materialization and a dense optimizer sweep every step.  On TPU the managed
win is additionally the (M, D)-vs-(T, D) all-reduce; the CPU numbers here
capture the sparse-update side of the story.

CLI: ``python -m benchmarks.kernels_bench [--quick]``.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.pipeline import SyntheticCorpus
from repro.kernels import ops, ref
from repro.pm.embedding import plain_lookup, pm_lookup
from repro.pm.planner import _bucket

from .common import time_fn


def _time(fn, *args, iters=5) -> float:
    return time_fn(lambda: fn(*args), iters=iters,
                   block=jax.block_until_ready)


def _managed_vs_plain(rows: List[str], *, V: int, D: int, B: int, S: int,
                      C: int, zipf_a: float, kernel_T: int) -> None:
    """Fwd+bwd+row-update step: managed (cache + deduped compact misses +
    sparse rows) vs plain (dense gather + dense grad + dense sweep)."""
    T = B * S
    corpus = SyntheticCorpus(V, zipf_a=zipf_a, seed=0)
    tokens = jnp.asarray(corpus.tokens((B, S)))
    tok = tokens.reshape(T).astype(jnp.int32)
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.normal(size=(V, D)), dtype=jnp.float32)
    accum = jnp.full((V, D), 0.1, dtype=jnp.float32)
    # the planner's replica cache: the C hottest rows of the skewed stream
    cache_ids = jnp.asarray(np.sort(corpus.perm[:C]), jnp.int32)
    cache_rows = jnp.take(table, cache_ids, axis=0)

    uniq = np.unique(np.asarray(tokens))
    n_miss = int(np.setdiff1d(uniq, np.asarray(cache_ids)).size)
    M = _bucket(max(1, n_miss))               # exact intent-derived bound
    hit_rate = float(np.isin(np.asarray(tok), np.asarray(cache_ids)).mean())

    @jax.jit
    def plain_step(table, accum):
        out = plain_lookup(table, tokens)
        gt = (2.0 * out).reshape(T, D)         # d/dtable of sum(out**2)
        grad = jnp.zeros((V, D), jnp.float32).at[tok].add(gt)
        a_new = accum + grad * grad            # dense AdaGrad sweep
        return table - 0.1 * grad / (jnp.sqrt(a_new) + 1e-8), a_new

    @jax.jit
    def managed_step(table, accum):
        out = pm_lookup(table, cache_ids, cache_rows, tokens, M, True)
        gt = (2.0 * out).reshape(T, D)
        # pad slots -> sentinel V: gathers clip, scatters drop (no-ops)
        ids, rows_g = ops.segment_rows(tok, gt, n_slots=T, pad_id=V)
        return ref.adagrad_row_update_ref(table, accum, ids, rows_g,
                                          lr=0.1, eps=1e-8)

    us_plain = _time(lambda: plain_step(table, accum), iters=10)
    us_managed = _time(lambda: managed_step(table, accum), iters=10)
    tag = f"zipf{zipf_a}_V{V}xD{D}xT{T}"
    rows.append(f"kernels,pm_plain_fwd_bwd,{tag},us_per_call,"
                f"{us_plain:.1f}")
    rows.append(f"kernels,pm_managed_fwd_bwd,{tag},us_per_call,"
                f"{us_managed:.1f}")
    rows.append(f"kernels,pm_managed_speedup,{tag},x,"
                f"{us_plain / us_managed:.2f}")
    rows.append(f"kernels,pm_hit_rate,{tag},frac,{hit_rate:.3f}")
    rows.append(f"kernels,pm_unique_miss,{tag},count,{n_miss}")

    # interpret-mode Pallas managed forward (correctness-path timing only;
    # native compilation is a TPU property) on a reduced token count
    ktok = tokens.reshape(T)[:kernel_T].reshape(1, kernel_T)

    @jax.jit
    def kernel_fwd(table):
        return pm_lookup(table, cache_ids, cache_rows, ktok, M, True, True)

    us_kernel = _time(lambda: kernel_fwd(table), iters=2)
    rows.append(f"kernels,pm_kernel_fwd_interp,{tag}_kT{kernel_T},"
                f"us_per_call,{us_kernel:.1f}")


def run(quick: bool = False) -> List[str]:
    rows: List[str] = []
    rng = np.random.default_rng(0)
    shapes = [(4096, 512, 256)] if quick else [(4096, 512, 256),
                                               (16384, 1024, 512)]
    for (V, D, n) in shapes:
        table = jnp.asarray(rng.normal(size=(V, D)), dtype=jnp.float32)
        accum = jnp.ones((V, D), dtype=jnp.float32)
        ids = jnp.asarray(rng.choice(V, size=(n,), replace=False),
                          dtype=jnp.int32)
        grads = jnp.asarray(rng.normal(size=(n, D)), dtype=jnp.float32)
        us_ref = _time(lambda: ref.embed_gather_ref(table, ids))
        rows.append(f"kernels,gather_ref,V{V}xD{D}xn{n},us_per_call,"
                    f"{us_ref:.1f}")
        us_ref2 = _time(lambda: ref.adagrad_row_update_ref(
            table, accum, ids, grads))
        rows.append(f"kernels,adagrad_ref,V{V}xD{D}xn{n},us_per_call,"
                    f"{us_ref2:.1f}")
        # analytic TPU bound: bytes over HBM bandwidth (gather: read+write
        # n*D; adagrad: 2 reads + 2 writes of n*D + grads read)
        gb = n * D * 4 * 2
        rows.append(f"kernels,gather_tpu_bound,V{V}xD{D}xn{n},us_roofline,"
                    f"{gb / 819e9 * 1e6:.2f}")
        ab = n * D * 4 * 5
        rows.append(f"kernels,adagrad_tpu_bound,V{V}xD{D}xn{n},us_roofline,"
                    f"{ab / 819e9 * 1e6:.2f}")

    # managed vs plain across Zipf skews (hotter skew -> higher hit rate
    # and fewer unique misses -> smaller compact buffer)
    if quick:
        dims = dict(V=32768, D=256, B=16, S=256, C=1024, kernel_T=64)
        skews = [1.1]
    else:
        dims = dict(V=65536, D=256, B=32, S=256, C=1024, kernel_T=128)
        skews = [1.05, 1.1, 1.5]
    for a in skews:
        _managed_vs_plain(rows, zipf_a=a, **dims)

    for r in rows:
        print(r)
    return rows


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized smoke (one shape, one skew)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run(quick=args.quick)
