"""Replans per dispatched batch in the traced window (serve.replans count)."""

import readers


def read(ctx):
    return readers.replans_per_batch(ctx)
