"""Row kernels' (embed_gather, pm_combine) share of the HBM roofline
over every chip of the mesh (%).

Bytes are those the lookup needs: every chip combines the whole
replicated batch (each token's row read and written), and each residual
miss row (the runtime's serve.prefetch_stale count) is read and written
once, by its owner's gather.  Seconds are the kernels' device time
summed over the same chips, so the share cannot pass 100%; with one
device it equals serve_max.row_kernels_roofline."""

from devtrace import op_seconds

KERNELS = ("embed_gather", "pm_combine")


def read(ctx):
    t = op_seconds(ctx.trace, KERNELS)
    if t <= 0:
        return None
    tokens = len(ctx.window_spans("serve.dispatch")) * ctx.tokens_per_batch
    rows = ctx.trace["devices"] * tokens \
        + sum(ctx.window_log("serve.prefetch_stale"))
    need_s = 2 * rows * ctx.row_bytes / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * need_s / t
