"""One run of one benchmark cell (see ``bench/run.py`` for the command).

The run:

1. finds the cell's files by name (``bench.cells``): its configuration,
   its traffic, and the driver that the configuration names
   (``bench.drivers``);
2. places JAX's persistent compilation cache inside the checkout, and
   refuses to go on unless JAX sees as many TPU chips as the cell asks
   for: there is no fallback;
3. calls the driver once for a warm-up chunk and then the window, a
   fixed number of equal chunks sized from ``--seconds``
   (``Cell.window_chunks``). Set-up runs from process start to the end
   of the warm-up chunk: JAX and TPU start-up, tracing, compiling or
   reading the cache, the initial lattices, the warm-up chunk. The
   window runs from there to the last chunk boundary;
4. reads the device's peak memory, then has the driver run its plain
   reference from the same seed and compare;
5. prints each number compared beside its limit as the last lines of
   standard error, and the result as the last line of standard output.

With ``--trace 1`` the window runs under the profiler, and the per-layer
metrics are read from the trace by readers found by name
(``bench/metrics/<metric>.py``).

Nothing here depends on a cell's shape: a new cell adds its
configuration, traffic, readers, engine streams and driver as files.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
import traceback
from collections import defaultdict
from types import SimpleNamespace

from . import cells, drivers, trace_reduce

# jax.monitoring duration events, by the part of set-up they time. The
# backend-compile event wraps the whole compile-or-read-the-cache call, so
# the cache read is a part of it, never added to it.
SETUP_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read_s",
}


class Monitor:
    """Collects JAX's own duration events with the host clock at which
    each ended."""

    def __init__(self):
        self.events = []

    def __call__(self, event, duration, **_):
        if event in SETUP_EVENTS:
            self.events.append((time.perf_counter(), SETUP_EVENTS[event],
                                float(duration)))

    def split(self, until: float) -> dict:
        out = defaultdict(float)
        for t, key, dur in self.events:
            if t <= until:
                out[key] += dur
        return dict(out)

    def compiles_between(self, lo: float, hi: float) -> int:
        return sum(1 for t, key, _ in self.events
                   if lo < t <= hi and key == "compile_s")

    def last_end(self, until: float) -> float:
        ends = [t for t, _, _ in self.events if t <= until]
        return max(ends) if ends else until


class Spans:
    """The benchmark's own host spans, written into the profiler's trace
    when tracing, and free otherwise."""

    def __init__(self, on: bool):
        self.on = on
        self.open = {}

    def begin(self, name: str):
        if self.on:
            from jax.profiler import TraceAnnotation  # noqa: PLC0415
            self.open[name] = TraceAnnotation(name)
            self.open[name].__enter__()

    def end(self, name: str):
        span = self.open.pop(name, None)
        if span is not None:
            span.__exit__(None, None, None)

    def mark(self, name: str):
        self.begin(name)
        self.end(name)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here (default: a "
                         "directory inside the checkout, removed after it "
                         "is read)")
    return ap.parse_args(argv)


def _reader(name: str, root: str):
    """``read(ctx)`` of the per-layer metric ``name``: the file
    ``bench/metrics/<name>.py`` of the checkout at ``root``, else of this
    package."""
    return cells.find_module("metrics", name, root).read


def _device_info(jax):
    devs = jax.devices()
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def main(argv=None, *, t_start=None, root=None,
         require_accelerator=True) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    cell = cells.load(args.workload, root)
    root = cell.root
    driver = drivers.of(cell)

    import jax  # noqa: PLC0415
    from repro.launch.compile_cache import enable_compile_cache  # noqa

    enable_compile_cache()
    devices = jax.devices()
    chips = int(cell.spec["chips"])
    if require_accelerator and (devices[0].platform != "tpu"
                                or len(devices) < chips):
        print(f"bench: {args.workload} needs {chips} TPU chip(s); JAX "
              f"sees {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    t_init = time.perf_counter()
    monitor = Monitor()
    jax.monitoring.register_event_duration_secs_listener(monitor)

    window_chunks = cell.window_chunks(args.seconds)
    n_chunks = 1 + window_chunks
    n_mcs = n_chunks * cell.chunk_mcs
    spans = Spans(bool(args.trace))
    trace_dir = args.trace_dir or os.path.join(
        root, ".bench_trace", f"{args.workload}.{args.seed}")
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)

    def on_boundary(i):
        # the profiler runs over the window alone: set-up's compiles would
        # fill the trace with host events, and set-up has its own split
        if i == 1 and args.trace:
            jax.profiler.start_trace(trace_dir)
            spans.begin(trace_reduce.WINDOW_SPAN)
        spans.mark("bench.hook")
        if i == n_chunks and args.trace:
            spans.end(trace_reduce.WINDOW_SPAN)
            jax.profiler.stop_trace()

    try:
        boundaries, got = driver.run(cell, args.seed, n_mcs, on_boundary)
    finally:
        jax.monitoring.unregister_event_duration_listener(monitor)
    device = _device_info(jax)

    w0, w1 = boundaries[0], boundaries[-1]
    window_s = w1 - w0
    window_mcs = window_chunks * cell.chunk_mcs
    window_updates = window_mcs * cell.updates_per_mcs
    setup = monitor.split(w0)
    setup.update(setup_s=w0 - t_start, init_s=t_init - t_start,
                 warmup_chunk_s=w0 - monitor.last_end(w0))
    window_compiles = monitor.compiles_between(w0, w1)

    t_ref = time.perf_counter()
    numbers = driver.check(cell, got,
                           driver.reference(cell, args.seed, n_mcs))
    ref_s = time.perf_counter() - t_ref
    limits = driver.LIMITS
    incomplete = got["mcs_completed"] != n_mcs
    correct = not incomplete and all(
        v <= limits[k] for k, v in numbers.items())
    attempted, failed = driver.answered(cell, got, numbers, n_mcs)

    metrics, breakdown = {}, None
    if args.trace:
        summary = trace_reduce.summarize(trace_reduce.find_xplane(trace_dir))
        if not args.trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
        device.update(busy_s=summary.busy_ns / 1e9,
                      window_s=summary.window_ns / 1e9)
        breakdown = {"device_ops": summary.top_ops,
                     "idle_gaps": summary.idle_gaps}
        ctx = SimpleNamespace(
            trace=summary, config=cell.config, traffic=cell.traffic,
            device_kind=device["kind"], window_mcs=window_mcs,
            window_updates=window_updates,
            setup_compile_s=setup.get("compile_s", 0.0))
        for m in cell.per_layer():
            value = _reader(m["name"], root)(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        host = {"site_updates_per_s": window_updates / window_s,
                "setup_s": setup["setup_s"]}
        for m in cell.end_to_end():
            metrics[m["name"]] = {"value": host[m["name"]],
                                  "unit": m["unit"]}

    result = {
        "correct": bool(correct), "attempted": int(attempted),
        "failed": int(failed), "metrics": metrics, "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result.update({
        "workload": args.workload, "seed": args.seed,
        "window": {"chunks": window_chunks, "mcs": window_mcs,
                   "updates": window_updates, "seconds": window_s,
                   "compiles": window_compiles},
        "setup_split_s": setup, "reference_s": ref_s,
        "check": {k: {"value": v, "limit": limits[k]}
                  for k, v in numbers.items()},
    })
    if incomplete:
        print(f"bench: the call ran {got['mcs_completed']} of {n_mcs} MCS",
              file=sys.stderr)
    for k, v in numbers.items():
        print(f"check {k} = {v} (limit {limits[k]})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def entry(t_start: float) -> int:
    try:
        return main(t_start=t_start)
    except Exception:  # noqa: BLE001 - a failed run prints no result
        traceback.print_exc()
        return 1
