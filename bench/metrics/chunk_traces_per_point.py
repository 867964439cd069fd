"""Traces of the chunk program per physics point of the call: the
program's ``traces`` over its ``points`` (``repro.core.tracing``). A
sweep that runs P points in one compiled program reads 1 / P; one call
per point reads 1. None where the program counts no traces."""


def read(ctx):
    try:
        from repro.core import tracing  # noqa: PLC0415
    except ImportError:
        return None
    run = tracing.last_run()
    totals = run.totals if run is not None else {}
    if "traces" not in totals or not totals.get("points"):
        return None
    return totals["traces"] / totals["points"]
