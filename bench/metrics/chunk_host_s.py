"""Mean seconds per window chunk of the driver's own Python work: the
program's ``escg.dispatch`` and ``escg.host_stats`` spans."""
import statistics

from bench import program_spans


def read(ctx):
    chunks = program_spans.window_chunks(ctx)
    if chunks is None:
        return None
    return statistics.fmean(
        c.spans["escg.dispatch"] + c.spans["escg.host_stats"]
        for c in chunks)
