#!/usr/bin/env python3
"""Record the small traces that ``test_trace_reduce.py`` reads. Run on a
TPU chip, from the root of the checkout:

    python3 bench/tests/record_traces.py bench/tests/data

Each of the benchmark's two cells runs once at test size (``bench_cases``)
with ``--trace 1``; the ``.xplane.pb`` is kept as ``<cell>.xplane.pb``,
with the checkout's path blanked, beside the reduction it gave,
``<cell>.summary.json``.
"""
import dataclasses
import json
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[0:1] = [HERE, ROOT, os.path.join(ROOT, "src")]

from bench import harness, trace_reduce  # noqa: E402
from bench_cases import LATTICE, TRIALS, small_root  # noqa: E402


def main(out_dir: str) -> int:
    os.makedirs(out_dir, exist_ok=True)
    with tempfile.TemporaryDirectory(dir=out_dir) as tmp:
        root = small_root(os.path.join(tmp, "root"))
        for cell in (f"{TRIALS}.sublattice", f"{LATTICE}.pallas_fused"):
            trace_dir = os.path.join(tmp, cell)
            rc = harness.main(["--workload", cell, "--seed", "1",
                               "--seconds", "1e-9", "--trace", "1",
                               "--trace-dir", trace_dir], root=root)
            if rc:
                return rc
            with open(trace_reduce.find_xplane(trace_dir), "rb") as f:
                data = f.read()
            dst = os.path.join(out_dir, f"{cell}.xplane.pb")
            with open(dst, "wb") as f:
                # source locations name the checkout: blank its path, at
                # the same length so that every length prefix still holds
                f.write(data.replace(ROOT.encode(),
                                     b"/" + b"_" * (len(ROOT) - 1)))
            summary = dataclasses.asdict(trace_reduce.summarize(dst))
            with open(os.path.join(out_dir, f"{cell}.summary.json"),
                      "w") as f:
                json.dump(summary, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
