"""Paper Table 4.2 — std of species-5 extinction probability across system
sizes and MCS horizons (the dissertation's multimodality audit of Park et
al.). Reduced: L in {16, 24}, MCS in {0, 200, 600}, 6 IID trials.

Every (L, MCS) cell is one invocation of the registered ``probabilistic``
scenario (the Park alliance physics live in ``core/scenarios.py``,
DESIGN.md §10) through the chunked, device-sharded trial driver
(``repro.core.trials`` via ``park.species5_extinction_std``): the Park
protocol — 2000 serial runs in the original — executes in device-parallel
chunks with streamed per-chunk statistics and per-trial stasis
early-exit."""
from __future__ import annotations

import time

from repro.core.park import species5_extinction_std
from repro.launch.compile_cache import enable_compile_cache

from .common import emit, note, smoke

LS = smoke((16,), (16, 24))
MCS = smoke((0, 100), (0, 200, 600))


def run() -> None:
    note("species-5 extinction std over (L, MCS) (paper Table 4.2), "
         "chunked trial driver")
    t0 = time.perf_counter()
    table = species5_extinction_std(LS, MCS, alpha=0.15, beta=0.75,
                                    gamma=1.0, n_trials=smoke(3, 6))
    dt = time.perf_counter() - t0
    for i, m in enumerate(MCS):
        row = " ".join(f"L{l}:{table[i, j]:.3f}" for j, l in enumerate(LS))
        emit(f"park_std_mcs{m}", dt / len(MCS), row)


if __name__ == "__main__":
    enable_compile_cache()
    run()
