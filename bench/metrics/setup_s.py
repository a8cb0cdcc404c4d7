"""Set-up: process start to the window's open (s)."""

import readers


def read(ctx):
    return ctx.setup_s
