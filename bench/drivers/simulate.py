"""Driver ``simulate``: one lattice through ``repro.core.simulate``.

Compared (``bench.compare``): the final lattice cell for cell
(``cells_differing``), and every step's species counts, as the result's
density stream holds them and as the driver streamed them to the hook
after every chunk, with, where the configuration streams it, every
step's interface length as an unlike-bond count
(``stream_rows_differing``, the initial row included).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from bench import compare, references
from bench.drivers import Boundaries, configs_of

LIMITS = {"stream_rows_differing": 0, "cells_differing": 0}


def run(cell, seed: int, n_mcs: int, on_boundary):
    from repro.core import simulate  # noqa: PLC0415

    cfg = cell.config
    scenario, engine, run_cfg = configs_of(cell, seed, n_mcs)
    stamp = Boundaries(on_boundary)
    res = simulate(scenario, engine=engine, run=run_cfg,
                   stop_on_stasis=False,
                   hooks=[lambda done, grid, cnts: stamp(cnts)])
    n_cells = cfg["height"] * cfg["length"]
    dens = np.rint(np.asarray(res.densities) * n_cells).astype(np.int64)
    return stamp.times, {
        "initial_counts": dens[0],
        "counts": dens[1:],
        "hook_counts": np.concatenate(stamp.streamed, axis=0),
        "interface_length": res.observables.get("interface_length"),
        "grid": np.asarray(res.grid),
        "mcs_completed": res.mcs_completed,
    }


def reference(cell, seed: int, n_mcs: int, dtype=jnp.float32):
    """``(counts, unlike bonds, final lattice)``."""
    return references.single(cell.config, cell.traffic["engine"], seed,
                             n_mcs, dtype, root=cell.root)


def check(cell, got: dict, ref) -> dict:
    counts, bonds, grid = ref
    return compare.check_single(got, counts, bonds, grid,
                                cell.config.get("observables", ()))


def answered(cell, got: dict, numbers: dict, n_mcs: int) -> tuple:
    """The answers are each step's counts, the initial row included, and
    the final lattice."""
    failed = (numbers["stream_rows_differing"]
              + (numbers["cells_differing"] > 0))
    return n_mcs + 1, failed


def as_answers(cell, ref) -> dict:
    return compare.single_as_answers(*ref)
