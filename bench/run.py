#!/usr/bin/env python3
"""Run one benchmark cell once, from the root of a checkout:

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

It needs a TPU with as many chips as the cell asks for, and exits non-zero
with no result line where JAX finds fewer. The last line of standard
output is the result as one JSON object; the numbers that decide
``correct`` come last on standard error, each beside its limit.
"""
import os
import sys
import time

T_START = time.perf_counter()

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]  # not bench/ itself

from bench.harness import entry  # noqa: E402

if __name__ == "__main__":
    sys.exit(entry(T_START))
