"""The system under test, driven through its public entry points.

A configuration names its driver: ``run_trials`` (an IID trial batch,
``repro.core.trials.run_trials``) or ``simulate`` (one lattice,
``repro.core.simulate``), both called scenario-first. One call runs the
warm-up chunk and the window back to back: the drivers fire their hooks
once per chunk, after the host has synced on that chunk, and those hooks
time the chunk boundaries. The stasis early exit is off, so that a run
does the same work whatever the dynamics do. Nothing else of the program
is touched.
"""
from __future__ import annotations

import time
from typing import Callable, List

import numpy as np


def scenario_of(cfg: dict):
    """The program's scenario for a configuration: the registered preset
    with its knobs, every physics field then set to the file's value."""
    from repro.core.scenarios import make_scenario  # noqa: PLC0415

    sc = make_scenario(cfg["scenario"], **cfg.get("scenario_knobs", {}))
    return sc.replace(species=cfg["species"],
                      neighbourhood=cfg["neighbourhood"],
                      mobility=cfg["mobility"], mu=cfg["mu"],
                      sigma=cfg["sigma"], epsilon=cfg["epsilon"],
                      empty=cfg["empty"], boundary=cfg["boundary"])


def configs_of(cell, seed: int, n_mcs: int):
    from repro.core.scenarios import EngineConfig, RunConfig  # noqa: PLC0415

    cfg, traffic = cell.config, cell.traffic
    engine = EngineConfig(engine=traffic["engine"],
                          cell_dtype=cfg["cell_dtype"],
                          tile=tuple(cfg["tile"]),
                          local_kernel=traffic.get("local_kernel", "jnp"),
                          k_mcs=int(traffic.get("k_mcs", 1)))
    observables = cfg.get("observables")   # absent: the scenario's own
    run = RunConfig(height=cfg["height"], length=cfg["length"], mcs=n_mcs,
                    chunk_mcs=cell.chunk_mcs, seed=seed,
                    observables=(None if observables is None
                                 else tuple(observables)))
    return scenario_of(cfg), engine, run


def run(cell, seed: int, n_mcs: int,
        on_boundary: Callable[[int], None] = lambda i: None):
    """One call of the cell's driver for ``n_mcs`` MCS. Returns the host
    clock at every chunk boundary and the answers the call produced, in
    the form ``bench.compare`` reads."""
    cfg = cell.config
    n_cells = cfg["height"] * cfg["length"]
    scenario, engine, run_cfg = configs_of(cell, seed, n_mcs)
    boundaries: List[float] = []
    streamed: list = []

    def boundary(payload):
        boundaries.append(time.perf_counter())
        streamed.append(np.array(payload))
        on_boundary(len(boundaries))

    if cfg["driver"] == "run_trials":
        from repro.core.trials import run_trials  # noqa: PLC0415

        res = run_trials(scenario, n_trials=cfg["trials"], engine=engine,
                         run=run_cfg, stop_on_stasis=False,
                         hooks=[lambda done, alive: boundary(alive)])
        answers = {
            "final_counts": np.rint(res.densities * n_cells).astype(
                np.int64),
            "alive": np.stack(streamed, axis=1),
            "extinction_mcs": res.extinction_mcs,
            "stasis_mcs": res.stasis_mcs,
            "survival": res.survival,
            "mcs_completed": res.mcs_completed,
        }
    elif cfg["driver"] == "simulate":
        from repro.core import simulate  # noqa: PLC0415

        res = simulate(scenario, engine=engine, run=run_cfg,
                       stop_on_stasis=False,
                       hooks=[lambda done, grid, cnts: boundary(cnts)])
        dens = np.rint(np.asarray(res.densities) * n_cells).astype(np.int64)
        answers = {
            "initial_counts": dens[0],
            "counts": dens[1:],
            "hook_counts": np.concatenate(streamed, axis=0),
            "interface_length": res.observables.get("interface_length"),
            "grid": np.asarray(res.grid),
            "mcs_completed": res.mcs_completed,
        }
    else:
        raise ValueError(f"unknown driver {cfg['driver']!r}")
    return boundaries, answers
