"""Driver ``run_trials``: an IID trial batch through
``repro.core.trials.run_trials``, all trials with the configuration's
physics.

Compared (``trials_differing``): a sample of trials drawn from the seed
(the traffic's ``check_sample``). For each, the final species counts,
the alive-species count streamed to the hook after every chunk, and the
extinction MCS, stasis MCS and survival (``bench.compare``).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from bench import compare, references
from bench.drivers import Boundaries, configs_of

LIMITS = {"trials_differing": 0}


def run(cell, seed: int, n_mcs: int, on_boundary):
    from repro.core.trials import run_trials  # noqa: PLC0415

    cfg = cell.config
    scenario, engine, run_cfg = configs_of(cell, seed, n_mcs)
    stamp = Boundaries(on_boundary)
    res = run_trials(scenario, n_trials=cfg["trials"], engine=engine,
                     run=run_cfg, stop_on_stasis=False,
                     hooks=[lambda done, alive: stamp(alive)])
    n_cells = cfg["height"] * cfg["length"]
    return stamp.times, {
        "final_counts": np.rint(res.densities * n_cells).astype(np.int64),
        "alive": np.stack(stamp.streamed, axis=1),
        "extinction_mcs": res.extinction_mcs,
        "stasis_mcs": res.stasis_mcs,
        "survival": res.survival,
        "mcs_completed": res.mcs_completed,
    }


def reference(cell, seed: int, n_mcs: int, dtype=jnp.float32):
    """``(sampled trial ids, their reference counts)``."""
    cfg = cell.config
    ids = compare.sample_trials(cfg["trials"], cell.traffic["check_sample"],
                                seed)
    return ids, references.trials(cfg, cell.traffic["engine"], seed, ids,
                                  n_mcs, dtype, root=cell.root)


def check(cell, got: dict, ref) -> dict:
    ids, counts = ref
    return compare.check_trials(got, counts, ids, cell.chunk_mcs)


def answered(cell, got: dict, numbers: dict, n_mcs: int) -> tuple:
    """The answers are the sampled trials."""
    attempted = min(cell.traffic["check_sample"], cell.config["trials"])
    return attempted, numbers["trials_differing"]


def as_answers(cell, ref) -> dict:
    ids, counts = ref
    return compare.trials_as_answers(counts, ids, cell.config["trials"],
                                     cell.chunk_mcs)
