"""Keys of every request served in the window over the window's length."""

import readers


def read(ctx):
    return readers.lookups_per_s(ctx)
