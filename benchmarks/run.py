"""Benchmark harness — one module per paper table/figure (DESIGN.md §7).

Prints ``name,us_per_call,derived`` CSV rows. Set BENCH_FAST=0 for the full
(slower) settings.
"""
from __future__ import annotations

import os
import sys
import time
import traceback

from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    # bench_gate is intentionally absent: it is the perf GATE, not a
    # figure — the CI perf-smoke job runs it standalone (with --out) and
    # would otherwise pay its engine-build sweep twice per run
    from . import (fig4_1_prng, fig4_2_batch_sweep, fig4_3_scaling,
                   fig4_4_variance, fig4_9_park_heatmap, roofline_table,
                   table4_2_park_stats, trials_throughput, zhong_density)
    t0 = time.time()
    if not os.environ.get("BENCH_JSON"):
        print("name,us_per_call,derived")   # CSV header; JSON rows need none
    failures = []
    for mod in (fig4_1_prng, fig4_2_batch_sweep, fig4_3_scaling,
                fig4_4_variance, zhong_density, fig4_9_park_heatmap,
                table4_2_park_stats, trials_throughput, roofline_table):
        print(f"# ===== {mod.__name__} =====", file=sys.stderr, flush=True)
        try:
            mod.run()
        except Exception as e:                          # noqa: BLE001
            # full traceback to stderr; keep stdout well-formed (a bare
            # ERROR line would corrupt a BENCH_JSON=1 row stream) and fail
            # the process so CI blames the right step
            failures.append(mod.__name__)
            traceback.print_exc(file=sys.stderr)
            if not os.environ.get("BENCH_JSON"):
                print(f"{mod.__name__},ERROR,{e}", flush=True)
    print(f"# total {time.time() - t0:.1f}s", file=sys.stderr)
    if failures:
        raise SystemExit(f"benchmark module(s) failed: {', '.join(failures)}")


if __name__ == "__main__":
    main()
