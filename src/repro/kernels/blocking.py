"""Tile-size selection for the row-blocked kernels: lane-aligned feature
padding, the HBM row group a DMA may move, and a small measured
autotuner.

Every row kernel in this package moves `(block_r, block_d)` tiles of row
data (multi-row tiling — the grid is ``(ceil(n / block_r), D' / block_d)``,
a ~block_r× smaller grid than the old one-row-per-program layout).  Three
decisions live here so the kernels stay mechanical:

  feature dim   ``pad_d`` rounds D up to the next multiple of the 128-lane
                VREG width.  Non-lane-aligned D (576, 570, ...) used to
                silently shrink the tile to the largest divisor (D=570 ->
                block 2 — a 285× grid blow-up); now the kernels pad the
                feature dim and keep full-lane tiles, slicing the pad off
                on the way out.  Lane-aligned D pays nothing; odd D pays
                full pad/slice copies of the row operands (and forfeits
                in-place donation for that call) — keep embedding dims
                lane-aligned on the hot path, padding is the correctness
                escape hatch.
  row group     A 2-D HBM array is stored in (8, 128) tiles of 32-bit
                words (16 rows per tile for bf16, 32 for 8-bit types), and
                Mosaic refuses a DMA whose row slice is not a whole number
                of tiles: a single-row copy out of a (V, D) table does not
                compile.  So the kernels DMA the ``row_group(dtype)`` rows
                of the tile holding the wanted row and pick the row inside
                VMEM (`kernels.rowdma`).  ``tile_pad`` pads an HBM operand
                to whole tiles — a no-op when V and D are already aligned.
  tile shape    `pick_blocks` answers (block_r, block_d) per
                (kind, n, d, dtype, backend).  The default is a cheap
                heuristic; when measurement is enabled the caller hands in
                a ``bench(block_r, block_d) -> seconds`` probe and the
                result is cached per key, so each shape is measured once
                per process.  ``block_r`` is always one the Pallas
                lowering accepts: a multiple of 8, or all ``n`` rows.

Measuring happens only on concrete operands (`measurable`): under
`jax.jit` tracing the operands are tracers, ``block_until_ready`` returns
at once and a timing would measure tracing, so the wrappers pass no probe
there and the heuristic answers (reported as ``source="heuristic"``).
Probes run on a zero table of at most ``n`` rows (`probe_operand`), never
on a copy of the full table.

Overrides, strongest first: `set_block_override()` (config hook used by
tests and launch scripts), then the ``REPRO_BLOCK_R`` / ``REPRO_BLOCK_D``
environment variables, then the autotuner cache.  ``REPRO_AUTOTUNE``
selects the tuning mode: ``auto`` (default — measure only on a real
accelerator backend, heuristic on CPU where interpret-mode timing is
meaningless), ``measure`` (always measure when a bench probe is given),
``off`` (heuristic only).
"""

from __future__ import annotations

import os
from typing import Callable, Dict, Optional, Tuple

LANE = 128            # VREG lane width: feature tiles are multiples of this
SUBLANE = 8           # rows of a 32-bit (8, 128) tile
DEFAULT_BLOCK_D = 512  # cap on the feature-tile width
DEFAULT_BLOCK_R = 8    # rows per program (multi-row tiling)
_ROW_CANDIDATES = (8, 16, 32)

_TUNE_CACHE: Dict[tuple, Tuple[int, int]] = {}
_OVERRIDE: Dict[str, Optional[int]] = {"block_r": None, "block_d": None}


def pad_d(d: int) -> int:
    """Feature dim rounded up to the next multiple of the 128-lane width
    (the kernels pad their row data to this and slice the pad off)."""
    return -(-d // LANE) * LANE


def row_group(dtype) -> int:
    """Rows of one HBM tile of ``dtype`` — the smallest row slice a DMA
    may move: 8 for 32-bit types, 16 for bf16, 32 for 8-bit types."""
    import numpy as np
    return SUBLANE * max(1, 4 // np.dtype(dtype).itemsize)


def tile_pad(x, group: int):
    """``x`` (R, D) zero-padded to whole HBM tiles: rows to a multiple of
    ``group``, columns to `pad_d`.  Returns ``x`` itself when aligned."""
    import jax.numpy as jnp
    r, d = x.shape
    rp, dp = -(-r // group) * group, pad_d(d)
    if (rp, dp) == (r, d):
        return x
    return jnp.pad(x, ((0, rp - r), (0, dp - d)))


def pick_block_d(d: int, block_d: int = DEFAULT_BLOCK_D) -> int:
    """Largest lane-multiple tile width that divides the *padded* feature
    dim and is <= the ``block_d`` cap (never below one 128-lane tile).

    The old rule returned the largest divisor of the raw D, so D=576
    shrank the tile to 288 and D=570 collapsed it to 2; padding keeps the
    tile full-width regardless of alignment."""
    lanes = pad_d(d) // LANE
    cap = max(1, block_d // LANE)
    best = 1
    for k in range(1, lanes + 1):
        if lanes % k == 0 and k <= cap:
            best = k
    return best * LANE


def legal_block_r(block_r: int, n: int) -> int:
    """The nearest row-tile height the Pallas TPU lowering accepts for an
    ``n``-row operand: a block's second-minor dim must be a multiple of 8
    or the whole dim.  Rounds down to a multiple of 8 (at least 8), and
    takes all ``n`` rows when that is no smaller."""
    n = max(1, n)
    br = max(SUBLANE, block_r // SUBLANE * SUBLANE)
    return n if br >= n else br


def set_block_override(block_r: Optional[int] = None,
                       block_d: Optional[int] = None) -> None:
    """Config hook: pin the tile shape globally (None clears a field).
    Takes effect for kernels traced after the call."""
    _OVERRIDE["block_r"] = block_r
    _OVERRIDE["block_d"] = block_d


def clear_autotune_cache() -> None:
    _TUNE_CACHE.clear()


def measurable(*xs) -> bool:
    """True when every operand is a concrete array, so a probe call can
    be timed; False while a caller is being traced."""
    import jax
    return not any(isinstance(x, jax.core.Tracer) for x in xs)


def probe_operand(n: int, n_rows: int, d: int, dtype):
    """Zero table and row ids for an autotune probe: at most ``n`` rows
    (bounded by the row operand the caller already holds, never the full
    table), ids spread over it — unique whenever ``n <= n_rows`` — so the
    timed DMA pattern is a scattered access, not n hits on row 0."""
    import jax.numpy as jnp
    rows = max(1, min(n, n_rows))
    return (jnp.zeros((rows, d), dtype),
            jnp.arange(n, dtype=jnp.int32) % rows)


def time_bench(fn: Callable, iters: int = 3) -> float:
    """Seconds per call of ``fn()`` (one untimed warmup/compile call) —
    the measurement probe the kernel wrappers hand to `pick_blocks`."""
    import time

    import jax
    jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def _env_int(name: str) -> Optional[int]:
    v = os.environ.get(name)
    return int(v) if v else None


def _measure_enabled(bench) -> bool:
    if bench is None:
        return False
    mode = os.environ.get("REPRO_AUTOTUNE", "auto")
    if mode == "off":
        return False
    if mode == "measure":
        return True
    # "auto": interpret-mode timings on CPU are meaningless; only measure
    # where the kernels compile natively
    import jax
    return jax.default_backend() == "tpu"


def pick_blocks(kind: str, n: int, d: int, dtype=None, *,
                table_rows: Optional[int] = None,
                block_r: Optional[int] = None,
                block_d: Optional[int] = None,
                bench: Optional[Callable[[int, int], float]] = None,
                ) -> Tuple[int, int]:
    """Tile shape for an (n, d) row kernel: explicit args win, then the
    `set_block_override` / env overrides, then the measured cache, then
    the heuristic.  Every answer passes through `legal_block_r`.
    ``bench(block_r, block_d) -> seconds`` enables the measured path (see
    module docstring for the mode switch; callers pass it only for
    concrete operands); results are cached per (kind, n, d, dtype,
    table_rows, backend).

    ``table_rows``: the height of the table-side operand (the gather /
    scatter / update target).  It MUST be part of the cache key: inside a
    `shard_map` the same (kind, n, d) call sees the shard-local
    ``V / n_shards`` block, and a tile measured against the full
    single-device V would otherwise be served stale to the mesh run (and
    vice versa)."""
    br = block_r if block_r is not None else \
        _OVERRIDE["block_r"] if _OVERRIDE["block_r"] is not None else \
        _env_int("REPRO_BLOCK_R")
    bd = block_d if block_d is not None else \
        _OVERRIDE["block_d"] if _OVERRIDE["block_d"] is not None else \
        _env_int("REPRO_BLOCK_D")
    bd = pick_block_d(d, bd if bd is not None else DEFAULT_BLOCK_D)
    if br is not None:
        return legal_block_r(br, n), bd

    import jax
    key = (kind, n, d, str(dtype), table_rows, jax.default_backend(), bd)
    if key in _TUNE_CACHE:
        return _TUNE_CACHE[key]
    if _measure_enabled(bench):
        timed = {}
        for cand in _ROW_CANDIDATES:
            cand = legal_block_r(cand, n)
            if cand not in timed:
                timed[cand] = bench(cand, bd)
        br = min(timed, key=lambda c: (timed[c], c))
        source = "measured"
    else:
        br = legal_block_r(DEFAULT_BLOCK_R, n)
        source = "heuristic"
    _TUNE_CACHE[key] = (br, bd)
    # every fresh tile decision lands on the process-wide signal bus
    # (one event per cache key: re-hits return above), so runs can audit
    # which shapes were measured vs. defaulted (DESIGN.md §13)
    from repro.obs.telemetry import default_bus
    default_bus().event("autotune.blocks", kind=kind, n=n, d=d,
                        block_r=br, block_d=bd, source=source)
    return br, bd
