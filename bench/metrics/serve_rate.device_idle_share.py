"""Share of the traced window with no operation on the device (%)."""

import readers


def read(ctx):
    return readers.idle_share_pct(ctx)
