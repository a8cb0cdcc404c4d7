"""95th percentile of due time to served, over all the window's requests (ms)."""

import readers


def read(ctx):
    return readers.percentile_ms(ctx, 95)
