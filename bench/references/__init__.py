"""Plain references of a lattice run and of an IID trial batch, over any
engine whose proposal streams have a module (``references/<engine>.py``,
found by the engine's name in the checkout at ``root`` first, else here).

``single`` follows one lattice from the seed the way a single-lattice run
does; ``trials`` follows chosen trials of an IID batch the way the trial
driver seeds them. Both return the species counts after every
Monte-Carlo step, and ``single`` the unlike-bond count after every step
and the final lattice. The drivers (``bench.drivers``) build their references on these.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import cells

from . import escg


def streams(engine: str, root: Optional[str] = None):
    """The module holding ``engine``'s proposal streams."""
    return cells.find_module("references", engine, root)


def _stepper(cfg: dict, engine: str, dtype, root):
    t_mig, t_int = escg.thresholds(cfg)
    dom = escg.dominance_matrix(cfg)
    mod = streams(engine, root)

    def step(grid, key):
        grid = mod.step(grid, key, cfg, t_mig=t_mig, t_int=t_int, dom=dom,
                        dtype=dtype)
        return grid, escg.counts(grid, cfg["species"])
    return step


def _init(cfg: dict, key):
    return escg.init_lattice(key, cfg["height"], cfg["length"],
                             cfg["species"], cfg["empty"])


def single(cfg: dict, engine: str, seed: int, n_mcs: int,
           dtype=jnp.float32, root: Optional[str] = None):
    """One lattice: the run key is ``PRNGKey(seed)``, split once for the
    initial lattice, then once per step for the step key. Returns
    ``(counts (n_mcs + 1, S + 1), unlike bonds (n_mcs,), final
    lattice)``; row 0 of the counts is the initial lattice, and the
    unlike-bond count is taken after every step."""
    inner = _stepper(cfg, engine, dtype, root)

    @jax.jit
    def step(grid, key):
        grid, cnt = inner(grid, key)
        return grid, cnt, escg.unlike_bonds(grid)

    key, k_init = jax.random.split(jax.random.PRNGKey(seed))
    grid = jax.jit(lambda k: _init(cfg, k))(k_init)
    rows = [np.asarray(escg.counts(grid, cfg["species"]))]
    bonds = []
    for _ in range(n_mcs):
        key, k_step = jax.random.split(key)
        grid, cnt, unlike = step(grid, k_step)
        rows.append(np.asarray(cnt))
        bonds.append(int(unlike))
    return np.stack(rows), np.asarray(bonds, np.int64), np.asarray(grid)


def trials(cfg: dict, engine: str, seed: int, trial_ids, n_mcs: int,
           dtype=jnp.float32, root: Optional[str] = None):
    """Trials ``trial_ids`` of a batch: trial t's key is ``fold_in(
    PRNGKey(seed), t)``, split into the initial-lattice key and the run
    key, which is split once per step. Returns counts (len(trial_ids),
    n_mcs + 1, S + 1). A driver whose trials play several physics points
    calls this once per point, on that point's ids."""
    step = jax.jit(jax.vmap(_stepper(cfg, engine, dtype, root)))
    base = jax.random.PRNGKey(seed)

    def start(t):
        k_init, k_run = jax.random.split(jax.random.fold_in(base, t))
        return _init(cfg, k_init), k_run

    grids, keys = jax.jit(jax.vmap(start))(
        jnp.asarray(np.asarray(trial_ids), jnp.int32))
    split = jax.jit(jax.vmap(jax.random.split))
    rows = [np.asarray(jax.vmap(lambda g: escg.counts(g, cfg["species"]))(
        grids))]
    for _ in range(n_mcs):
        both = split(keys)
        keys, k_step = both[:, 0], both[:, 1]
        grids, cnt = step(grids, k_step)
        rows.append(np.asarray(cnt))
    return np.stack(rows, axis=1)
