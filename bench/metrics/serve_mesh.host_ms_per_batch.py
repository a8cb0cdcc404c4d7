"""Host ms per executed batch: serve.enqueue + serve.probe + serve.dispatch spans over batches."""

import readers


def read(ctx):
    return readers.host_ms_per_batch(ctx)
