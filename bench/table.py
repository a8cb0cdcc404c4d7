"""The served table, made from the seed, and its plain reference.

Entry ``(row, col)`` of the table is a counter-based hash of the seed,
the row and the column, mapped to a float32 in [-1, 1) with 24 bits.
The device builds the whole table in one jitted call; the reference
recomputes any row on the host with numpy, bit for bit, without reading
anything the program made.
"""

from __future__ import annotations

import numpy as np

_M_ROW = 0x9E3779B1
_M_COL = 0x85EBCA77
_M_SEED = 0x27D4EB2F
_F1 = 0x85EBCA6B
_F2 = 0xC2B2AE35
_SCALE = 2.0 ** -23


def seed_words(seed: int):
    """Two uint32 words of a non-negative seed of up to 64 bits."""
    seed = int(seed) % (1 << 64)
    return np.uint32(seed & 0xFFFFFFFF), np.uint32(seed >> 32)


def _fmix(xp, h):
    """MurmurHash3's 32-bit finalizer (wrapping uint32 arithmetic)."""
    h = h ^ (h >> 16)
    h = h * xp.uint32(_F1)
    h = h ^ (h >> 13)
    h = h * xp.uint32(_F2)
    return h ^ (h >> 16)


def _values(xp, rows, cols, s0, s1):
    """Table entries for broadcastable uint32 ``rows`` x ``cols``."""
    h = _fmix(xp, rows * xp.uint32(_M_ROW) + s0)
    h = _fmix(xp, h ^ (cols * xp.uint32(_M_COL) + (s1 ^ xp.uint32(_M_SEED))))
    return (h >> 8).astype(xp.float32) * xp.float32(_SCALE) - xp.float32(1.0)


def reference_rows(keys, dim: int, seed: int) -> np.ndarray:
    """The plain reference: float32 rows ``table[keys]`` on the host."""
    s0, s1 = seed_words(seed)
    keys = np.asarray(keys, np.int64).astype(np.uint32)
    cols = np.arange(dim, dtype=np.uint32)
    with np.errstate(over="ignore"):
        return _values(np, keys[..., None], cols, s0, s1)


def make_table(rows: int, dim: int, seed: int, dtype: str = "float32",
               sharding=None):
    """The (rows, dim) table on the device, built in one jitted call.

    The seed enters as two traced words, so every seed reuses one
    compiled program.  ``sharding`` places the output (a row-sharded
    table is built in place on its devices)."""
    import jax
    import jax.numpy as jnp

    def build(s0, s1):
        r = jax.lax.broadcasted_iota(jnp.uint32, (rows, dim), 0)
        c = jax.lax.broadcasted_iota(jnp.uint32, (rows, dim), 1)
        return _values(jnp, r, c, s0, s1).astype(dtype)

    fn = jax.jit(build, out_shardings=sharding)
    s0, s1 = seed_words(seed)
    return jax.block_until_ready(fn(jnp.uint32(s0), jnp.uint32(s1)))
