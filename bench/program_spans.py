"""The program's own chunk records (``repro.core.tracing``) of the window.

The drivers keep one record per consumed chunk of their last call: the
seconds in each span of the chunk boundary and the number of blocking
device-to-host reads. The window's chunks are the last ones of the call.
"""


def window_chunks(ctx):
    """The records of the window's chunks, oldest first; None where the
    program keeps no such records, or holds fewer than the window's."""
    try:
        from repro.core import tracing  # noqa: PLC0415
    except ImportError:
        return None
    run = tracing.last_run()
    n = ctx.window_mcs // int(ctx.config["chunk_mcs"])
    if run is None or n < 1 or len(run.chunks) < n:
        return None
    return list(run.chunks)[-n:]

