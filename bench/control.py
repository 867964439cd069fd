#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``.

The plain reference, computed one precision below what the
configurations state (bfloat16 in place of float32 for every rate,
threshold and random draw), is put in the program's place and compared
with the float32 reference exactly as a run's answers are. A comparison
that passes this control cannot tell a lower-precision path from a sound
one. Benchmark runs never run it. On the chip, at a cell's own size:

    python3 bench/control.py --workload <name> --seconds <run_seconds> --seeds 1 2 3

One JSON line per seed, with each number compared beside its limit.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]  # not bench/ itself


def control_numbers(cell, seed: int, n_mcs: int) -> dict:
    """The cell's compared numbers with the lower-precision reference in
    the program's place, through the cell's driver."""
    import jax.numpy as jnp  # noqa: PLC0415
    from bench import drivers  # noqa: PLC0415

    driver = drivers.of(cell)
    sound = driver.reference(cell, seed, n_mcs, jnp.float32)
    low = driver.reference(cell, seed, n_mcs, jnp.bfloat16)
    return driver.check(cell, driver.as_answers(cell, low), sound)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    import jax  # noqa: PLC0415
    from bench import cells, drivers  # noqa: PLC0415

    cell = cells.load(args.workload)
    limits = drivers.of(cell).LIMITS
    n_mcs = (1 + cell.window_chunks(args.seconds)) * cell.chunk_mcs
    dev = jax.devices()[0]
    for seed in args.seeds:
        t0 = time.perf_counter()
        numbers = control_numbers(cell, seed, n_mcs)
        print(json.dumps({
            "workload": args.workload, "seed": seed, "mcs": n_mcs,
            "device": {"platform": dev.platform, "kind": dev.device_kind},
            "seconds": time.perf_counter() - t0,
            "check": {k: {"value": v, "limit": limits[k]}
                      for k, v in numbers.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
