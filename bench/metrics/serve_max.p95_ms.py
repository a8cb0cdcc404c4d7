"""95th percentile of enqueue to served in the closed loop (ms)."""

import readers


def read(ctx):
    return readers.percentile_ms(ctx, 95, since="enqueue")
