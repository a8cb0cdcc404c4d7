"""95th percentile of due time to the runtime's enqueue stamp (ms)."""

import readers


def read(ctx):
    return readers.admit_wait_ms(ctx, 95)
