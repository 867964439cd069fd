"""Share of the window the host spent waiting for the device at chunk
boundaries: the program's ``escg.wait`` seconds of the window's chunks
over the window span. The host's side of ``device_idle_share``."""
from bench import program_spans


def read(ctx):
    chunks = program_spans.window_chunks(ctx)
    if chunks is None or ctx.trace is None or not ctx.trace.window_ns:
        return None
    wait_s = sum(c.spans["escg.wait"] for c in chunks)
    return wait_s / (ctx.trace.window_ns / 1e9)
