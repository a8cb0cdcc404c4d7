"""Mean token miss rate of the window's batches, from serve.miss_rate (%)."""

import readers


def read(ctx):
    return readers.mean_gauge_pct(ctx, "serve.miss_rate")
