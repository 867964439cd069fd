"""Paper Figs 4.9/4.10 — Park et al. survival-count probabilities over the
(alpha, beta) plane, gamma = 1 (reduced resolution/trials for CPU).

Paper protocol: L=100, terminate after L^2 MCS, many IID runs. Here a
coarse grid at L=32 with vmapped trials; emits the survivors histogram per
(alpha, beta) cell. Each cell is one invocation of the registered
``probabilistic`` scenario (``core/scenarios.py``, DESIGN.md §10) with its
(alpha, beta, gamma) rate knobs. benchmarks/run.py keeps this to a 3x3
grid; examples/park_alliances.py exposes the full sweep.
"""
from __future__ import annotations

import numpy as np

from repro.core.park import survival_probabilities
from repro.launch.compile_cache import enable_compile_cache

from .common import emit, note, smoke, time_fn

GRID = smoke((0.5,), (0.1, 0.5, 0.9))
L = smoke(16, 32)
TRIALS = smoke(2, 8)


def run() -> None:
    note(f"Park (alpha,beta) sweep at L={L}, {TRIALS} vmapped IID trials "
         f"per cell, {L*L} MCS (paper Figs 4.9/4.10)")
    import time
    for alpha in GRID:
        for beta in GRID:
            t0 = time.perf_counter()
            ps, hist = survival_probabilities(
                alpha, beta, 1.0, L=L, n_trials=TRIALS, mcs=L * L)
            dt = time.perf_counter() - t0
            mode = int(np.argmax(hist))
            emit(f"park_a{alpha}_b{beta}", dt,
                 f"mode_survivors {mode}; hist "
                 + "|".join(f"{v:.2f}" for v in hist))


if __name__ == "__main__":
    enable_compile_cache()
    run()
