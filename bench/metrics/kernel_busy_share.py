"""Share of the device's busy time spent in Pallas kernels (custom calls)
rather than XLA's own ops."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.kernel_ns or not ctx.trace.busy_ns:
        return None
    return ctx.trace.kernel_ns / ctx.trace.busy_ns
