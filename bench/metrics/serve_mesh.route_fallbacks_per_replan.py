"""Routed gathers that took the replicated-psum arm (the runtime's
serve.route_fallbacks count, over batches, replica refreshes and staging
gathers) per replan (serve.plan span) in the traced window.  None where
the run recorded no such count (a program without it)."""

COUNT = "serve.route_fallbacks"


def read(ctx):
    if not any(n == COUNT for _, n, _ in ctx.bus_log):
        return None
    plans = len(ctx.window_spans("serve.plan"))
    if not plans:
        return None
    return sum(ctx.window_log(COUNT)) / plans
