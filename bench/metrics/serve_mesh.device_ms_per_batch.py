"""A chip's busy time, averaged over the chips, per executed batch in
the traced window (ms)."""

import readers


def read(ctx):
    return readers.device_ms_per_batch(ctx)
