"""Mean number of blocking device-to-host reads per window chunk: the
program's ``syncs`` count of each chunk."""
import statistics

from bench import program_spans


def read(ctx):
    chunks = program_spans.window_chunks(ctx)
    if chunks is None:
        return None
    return statistics.fmean(c.syncs for c in chunks)
