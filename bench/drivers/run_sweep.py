"""Driver ``run_sweep``: a sweep of physics points as one IID trial
batch, through one ``repro.core.trials.run_trials`` call given one
scenario per entry of the configuration's ``points``.

The configuration's ``trials`` is the whole batch, split evenly over the
points, point-major: trial ``q * n + i`` is trial i of point q, where n
is ``trials`` over the number of points. Each entry of ``points`` states
the knobs and dominance edges of its point in full, over the file's
top-level physics. Trial i of every point starts from the lattice and
random streams of trial i of a single-point call from the same seed.

Compared (``trials_differing``) as the ``run_trials`` driver compares: a
sample of the batch's trials drawn from the seed, each followed by the
plain reference under its own point's physics (``references.trials``
once per point, on that point's sampled trials).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from bench import compare, references
from bench.drivers import Boundaries, configs_of, scenario_of
from bench.drivers import run_trials as batch

LIMITS = batch.LIMITS
check, answered, as_answers = batch.check, batch.answered, batch.as_answers


def points(cfg: dict):
    """``(trials per point, each point's configuration)``: the file's,
    with the point's entry over it and its share of the trials."""
    per_point = cfg["trials"] // len(cfg["points"])
    return per_point, [dict(cfg, trials=per_point, **point)
                       for point in cfg["points"]]


def run(cell, seed: int, n_mcs: int, on_boundary):
    from repro.core.trials import run_trials  # noqa: PLC0415

    cfg = cell.config
    per_point, cfgs = points(cfg)
    _, engine, run_cfg = configs_of(cell, seed, n_mcs)
    stamp = Boundaries(on_boundary)
    res = run_trials([scenario_of(c) for c in cfgs], n_trials=per_point,
                     engine=engine, run=run_cfg, stop_on_stasis=False,
                     hooks=[lambda done, alive: stamp(alive)])
    n_cells = cfg["height"] * cfg["length"]

    def joined(field):
        return np.concatenate([getattr(r, field) for r in res])
    return stamp.times, {
        "final_counts": np.rint(joined("densities") * n_cells).astype(
            np.int64),
        "alive": np.stack(stamp.streamed, axis=1),
        "extinction_mcs": joined("extinction_mcs"),
        "stasis_mcs": joined("stasis_mcs"),
        "survival": joined("survival"),
        "mcs_completed": min(r.mcs_completed for r in res),
    }


def reference(cell, seed: int, n_mcs: int, dtype=jnp.float32):
    """``(sampled trial ids, their reference counts)``, each sampled
    trial under its own point's physics."""
    cfg = cell.config
    per_point, cfgs = points(cfg)
    ids = compare.sample_trials(cfg["trials"], cell.traffic["check_sample"],
                                seed)
    parts = []
    for q, point in enumerate(cfgs):
        mine = ids[ids // per_point == q] % per_point
        if len(mine):
            parts.append(references.trials(point, cell.traffic["engine"],
                                           seed, mine, n_mcs, dtype,
                                           root=cell.root))
    return ids, np.concatenate(parts)
