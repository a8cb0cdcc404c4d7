"""Device ms per dispatched batch in collective operations, per chip:
the device seconds of the operations whose HLO name holds all-gather,
all-reduce, all-to-all, collective-permute or reduce-scatter, summed
over the traced window's device planes, over the planes, over the
runtime's serve.dispatch spans in the window."""

from devtrace import op_seconds

COLLECTIVES = ("all-gather", "all-reduce", "all-to-all",
               "collective-permute", "reduce-scatter")


def read(ctx):
    batches = len(ctx.window_spans("serve.dispatch"))
    if not ctx.trace or not batches:
        return None
    t = op_seconds(ctx.trace, COLLECTIVES)
    return 1e3 * t / ctx.trace["devices"] / batches
