"""Share of the window in which no operation ran on the device: 1 minus
the union of the device's op intervals over the window span."""


def read(ctx):
    if ctx.trace is None:
        return None
    return ctx.trace.idle_share
