"""Row kernels' (embed_gather, pm_combine) share of the HBM roofline (%)."""

import readers


def read(ctx):
    return readers.row_kernels_roofline_pct(
        ctx, ("embed_gather", "pm_combine"))
