"""Device busy time per useful elementary update in the window: the union
of the device's op intervals over the updates the window's chunks made."""


def read(ctx):
    if ctx.trace is None or not ctx.window_updates:
        return None
    return ctx.trace.busy_ns / ctx.window_updates
