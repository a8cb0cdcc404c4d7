"""Share of the traced window with no operation on a chip, averaged over
the chips (%)."""

import readers


def read(ctx):
    return readers.idle_share_pct(ctx)
