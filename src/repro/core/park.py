"""Park, Chen & Szolnoki (2023) eight-species alliance model (paper §4.3.2)
plus the mobility extension of the Cliff & Sinadjan companion paper (App. C).

Since the scenario layer (DESIGN.md §10) this module is a thin invocation
of the registered ``probabilistic`` scenario: the physics (S=8, the
(alpha, beta, gamma) rate network, epsilon=0 unless the companion paper's
mobility knob is turned) lives in ``core.scenarios``; here we only compose
it with an engine/run config and stream the trial statistics the figures
read.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np

from .params import EscgParams
from .scenarios import EngineConfig, RunConfig, Scenario, make_scenario
from .trials import run_trials


def park_scenario(alpha: float = 0.15, beta: float = 0.75,
                  gamma: float = 1.0, mobility: float = 0.0) -> Scenario:
    """The registered ``probabilistic`` preset with Park's rate knobs."""
    return make_scenario("probabilistic", alpha=alpha, beta=beta,
                         gamma=gamma, mobility=mobility)


def park_params(L: int = 100, mcs: Optional[int] = None,
                mobility: float = 0.0, engine: str = "batched",
                seed: int = 0, **kw) -> EscgParams:
    """Paper/Park defaults: S=8, no empties... Park's model has no empty
    sites initially; interactions produce empties which reproduction refills.
    Terminates after L^2 MCS (paper Fig 4.9/4.10). Back-compat facade:
    composes the ``probabilistic`` scenario and applies ``**kw`` as flat
    ``EscgParams`` overrides."""
    p = park_scenario(mobility=mobility).to_legacy(
        EngineConfig(engine=engine),
        RunConfig(length=L, height=L, seed=seed,
                  mcs=int(mcs if mcs is not None else L * L)))
    return p.replace(**kw).validate() if kw else p


def survival_probabilities(alpha: float, beta: float, gamma: float = 1.0,
                           L: int = 100, n_trials: int = 20,
                           mcs: Optional[int] = None, mobility: float = 0.0,
                           key: Optional[jax.Array] = None,
                           engine: str = "batched",
                           trial_devices: Optional[int] = None
                           ) -> Tuple[np.ndarray, np.ndarray]:
    """Returns (per-species survival probability [8], n-survivors histogram
    [9]) over device-sharded IID trials — the quantity behind paper Figs
    4.9-4.13. One scenario invocation: the trial driver derives the
    (alpha, beta, gamma) dominance network from the scenario registry and
    runs device-parallel chunks with streamed per-chunk statistics
    (trials.run_trials); stasis early-exit is safe here because a species
    can never re-appear after stasis, so the survival mask is frozen from
    that point on."""
    return survival_probabilities_sweep(
        [(alpha, beta)], gamma, L=L, n_trials=n_trials, mcs=mcs,
        mobility=mobility, key=key, engine=engine,
        trial_devices=trial_devices)[0]


def survival_probabilities_sweep(points: Sequence[Tuple[float, float]],
                                 gamma: float = 1.0, L: int = 100,
                                 n_trials: int = 20,
                                 mcs: Optional[int] = None,
                                 mobility: float = 0.0,
                                 key: Optional[jax.Array] = None,
                                 engine: str = "batched",
                                 trial_devices: Optional[int] = None
                                 ) -> List[Tuple[np.ndarray, np.ndarray]]:
    """:func:`survival_probabilities` at every (alpha, beta) of ``points``,
    as ONE trial batch of ``len(points) x n_trials`` lattices: one chunk
    program for the whole grid of a figure, each point's rates operands
    of the engine (``trials.run_trials`` over several scenarios; for more
    than one point the engine must take its physics as an operand, as
    ``batched``, ``reference`` and ``sublattice`` do). Point p's numbers
    are those of ``survival_probabilities(*points[p], ...)`` with the
    same key: trial i of every point starts from the same lattice and
    random streams. Returns one (survival probability [8], n-survivors
    histogram [9]) per point, in order."""
    scs = [park_scenario(a, b, gamma, mobility) for a, b in points]
    res = run_trials(scs, None, n_trials, key=key,
                     trial_devices=trial_devices,
                     engine_config=EngineConfig(engine=engine),
                     run_config=RunConfig(
                         length=L, height=L,
                         mcs=int(mcs if mcs is not None else L * L)))
    return [(r.survival_probabilities(), r.survivors_hist()) for r in res]


def species5_extinction_std(L_values, mcs_values, alpha: float = 0.15,
                            beta: float = 0.75, gamma: float = 1.0,
                            n_trials: int = 20, seed: int = 0,
                            engine: str = "batched",
                            trial_devices: Optional[int] = None
                            ) -> np.ndarray:
    """Replication of paper Table 4.2: std of species-5 extinction indicator
    across IID trials, for each (MCS, L). Returns (len(mcs), len(L)).

    Each cell is one scenario invocation through the chunked,
    device-sharded driver, so the Park protocol (2000 serial runs in the
    original) executes in device-parallel chunks with streamed
    statistics."""
    out = np.zeros((len(mcs_values), len(L_values)))
    sc = park_scenario(alpha, beta, gamma)
    for j, L in enumerate(L_values):
        for i, mcs in enumerate(mcs_values):
            if mcs == 0:
                out[i, j] = 0.0
                continue
            res = run_trials(sc, None, n_trials,
                             key=jax.random.PRNGKey(seed + 17 * j + i),
                             trial_devices=trial_devices,
                             engine_config=EngineConfig(engine=engine),
                             run_config=RunConfig(length=L, height=L,
                                                  mcs=mcs, seed=seed))
            extinct5 = 1.0 - res.survival[:, 4].astype(np.float64)
            out[i, j] = float(extinct5.std())
    return out
