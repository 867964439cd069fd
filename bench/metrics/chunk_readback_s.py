"""Mean seconds per window chunk in the program's ``escg.readback`` span:
the device-to-host reads of the chunk's outputs, after the device has
finished them. A copy that stalls shows here."""
import statistics

from bench import program_spans


def read(ctx):
    chunks = program_spans.window_chunks(ctx)
    if chunks is None:
        return None
    return statistics.fmean(c.spans["escg.readback"] for c in chunks)
