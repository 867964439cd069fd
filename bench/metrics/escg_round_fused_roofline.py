"""Share, in percent, of the HBM roofline that the fused Pallas round
reaches: the least time its bytes take at the chip's published HBM
bandwidth (``bench.peaks``: every lattice read once and written once per
MCS), over the Pallas kernel time of the window. The round has no
published integer-op peak, so the bound is bytes alone."""
from bench import peaks


def read(ctx):
    if ctx.trace is None or not ctx.trace.kernel_ns:
        return None
    least_s = (peaks.work_bytes_per_mcs(ctx.config) * ctx.window_mcs
               / peaks.peak(ctx.device_kind, "hbm_bytes_per_s"))
    return 100.0 * least_s / (ctx.trace.kernel_ns / 1e9)
