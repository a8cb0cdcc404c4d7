"""Host ms per replan spent dispatching the replica refresh: the summed
length of the runtime's serve.refresh spans in the window over its
serve.plan spans there.  None where the run recorded no serve.refresh
span (a program without it)."""

SPAN = "serve.refresh"


def read(ctx):
    if not any(s[0] == SPAN for s in ctx.spans):
        return None
    plans = len(ctx.window_spans("serve.plan"))
    if not plans:
        return None
    return sum(s[2] - s[1] for s in ctx.window_spans(SPAN)) / 1e6 / plans
