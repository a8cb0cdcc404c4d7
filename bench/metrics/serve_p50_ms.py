"""Median of due time to served, over the window's requests (ms)."""

import readers


def read(ctx):
    return readers.percentile_ms(ctx, 50)
