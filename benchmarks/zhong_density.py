"""Paper Figs 3.2/3.3/3.7/3.8 — Zhong et al. ablated-RPSLS density
dynamics: the Paper species must go extinct early (200-600 MCS at L=200;
earlier at reduced L), leaving the Rock-Lizard-Spock / Scissors-Lizard-
Spock sub-cycles. Run per engine to show cross-engine stochastic validity
(paper §4.1).

Since the scenario layer (DESIGN.md §10) this is a thin scenario
invocation: the physics (ablated-RPSLS dominance, mobility, S=5) come from
the registered ``zhong_density`` preset; the module only picks engines and
run control. Runs through the chunked trial driver (``repro.core.trials``):
a small IID batch per engine, extinction MCS streamed per chunk instead of
a full density history — the per-trial ``extinction_mcs`` statistic is
exactly the paper's observable."""
from __future__ import annotations

import time

import numpy as np

from repro.core import dominance as dm
from repro.core.scenarios import EngineConfig, RunConfig, make_scenario
from repro.core.trials import run_trials
from repro.launch.compile_cache import enable_compile_cache

from .common import emit, note, smoke

L, MCS, TRIALS = smoke(32, 64), smoke(200, 1200), smoke(2, 3)


def run() -> None:
    note(f"Zhong ablated RPSLS at L={L}, {MCS} MCS, {TRIALS} IID trials "
         "(paper Fig 3.2)")
    sc = make_scenario("zhong_density")
    for engine in ("batched", "sublattice"):
        t0 = time.perf_counter()
        res = run_trials(
            sc, None, TRIALS, stop_on_stasis=False,
            engine=EngineConfig(engine=engine, tile=(8, 16)),
            run=RunConfig(length=L, height=L, mcs=MCS,
                          chunk_mcs=300, seed=11))
        dt = time.perf_counter() - t0
        ext = res.extinction_mcs[:, dm.PAPER - 1]       # per-trial, exact MCS
        ext_str = ("/".join(str(int(e)) for e in ext))
        alive = res.survival.sum(axis=1)
        emit(f"zhong_{engine}", dt,
             f"paper_extinct_mcs {ext_str}; "
             f"alive_end {alive.min()}-{alive.max()}; "
             f"rock_end {np.mean(res.densities[:, dm.ROCK]):.3f}")


if __name__ == "__main__":
    enable_compile_cache()
    run()
