"""Drivers: how a cell calls the system under test, found by name.

A configuration names its driver (``"driver"``): the module
``bench/drivers/<driver>.py`` of the checkout at the cell's root, else of
this package. A driver module owns everything that depends on the shape
of what it drives:

- ``run(cell, seed, n_mcs, on_boundary) -> (boundaries, answers)``: one
  call of the program, scenario-first through its public entry points,
  for the warm-up chunk and the window back to back. The program fires
  its hooks once per chunk, after the host has synced on that chunk;
  the driver stamps the host clock there (``Boundaries``) and calls
  ``on_boundary`` with the boundary's number. The stasis early exit is
  off, so that a run does the same work whatever the dynamics do. The
  answers carry ``mcs_completed``;
- ``reference(cell, seed, n_mcs, dtype)``: the plain reference's answers
  for the run (``bench.references``), computed in ``dtype``;
- ``check(cell, got, ref) -> {number: value}``: every number compared,
  each with its limit in the module's ``LIMITS``;
- ``answered(cell, got, numbers, n_mcs) -> (attempted, failed)``: the
  answers due and the answers wrong;
- ``as_answers(cell, ref)``: reference output dressed as ``run``'s
  answers, so that a reference computed lower can stand in the program's
  place (``bench/control.py``).

The parts that drivers share sit here and in ``bench.compare``.
"""
from __future__ import annotations

import time
from typing import Callable, List, Optional

import numpy as np

from bench import cells


def find(name: str, root: Optional[str] = None):
    """The driver module ``name``, from the checkout at ``root`` first."""
    return cells.find_module("drivers", name, root)


def of(cell):
    """The driver module that the cell's configuration names."""
    return find(cell.config["driver"], cell.root)


def run(cell, seed: int, n_mcs: int,
        on_boundary: Callable[[int], None] = lambda i: None):
    """One call of the cell's driver for ``n_mcs`` MCS: the host clock at
    every chunk boundary and the answers the call produced."""
    return of(cell).run(cell, seed, n_mcs, on_boundary)


class Boundaries:
    """A program hook's stamps: the host clock at each chunk boundary and
    a copy of what the hook was handed there."""

    def __init__(self, on_boundary: Callable[[int], None]):
        self.times: List[float] = []
        self.streamed: list = []
        self._on_boundary = on_boundary

    def __call__(self, payload):
        self.times.append(time.perf_counter())
        self.streamed.append(np.array(payload))
        self._on_boundary(len(self.times))


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
    """``(scenario, EngineConfig, RunConfig)`` of one call of the cell."""
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
