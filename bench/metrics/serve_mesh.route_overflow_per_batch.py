"""Tokens per dispatched batch that only the mesh's per-owner admission
bound flagged (the runtime's serve.route_overflow_tokens count, one
write a batch) in the traced window.  None where the run recorded no
such count (a program without it)."""

COUNT = "serve.route_overflow_tokens"


def read(ctx):
    if not any(n == COUNT for _, n, _ in ctx.bus_log):
        return None
    batches = len(ctx.window_spans("serve.dispatch"))
    if not batches:
        return None
    return sum(ctx.window_log(COUNT)) / batches
