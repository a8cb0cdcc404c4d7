"""Mean length of the runtime's serve.plan spans in the window (ms)."""

import readers


def read(ctx):
    return readers.mean_span_ms(ctx, "serve.plan")
