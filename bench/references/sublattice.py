"""Reference proposal streams of the ``sublattice`` engine: per-tile
threefry streams (arXiv:2508.16639 §3.2.1 batches of random numbers, with
one counter-based stream per tile).

Per Monte-Carlo step with key ``k``: ``kp, ks = split(k)``; tile ``t``
draws its K proposals from ``split(fold_in(kp, t), 4)`` as (interior cell,
direction, action draw, dominance draw) with ``randint``, ``randint``,
``uniform``, ``uniform``; the torus shift is ``randint(ks, (2,), 0, (th,
tw))``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import escg


def step(grid, key, cfg, *, t_mig, t_int, dom, dtype=jnp.float32):
    """One Monte-Carlo step of one lattice under the step key ``key``."""
    th, tw = cfg["tile"]
    n_tiles, k, interior = escg.tiling(cfg)
    nbhd = cfg["neighbourhood"]
    kp, ks = jax.random.split(key)

    def tile_stream(t):
        k1, k2, k3, k4 = jax.random.split(jax.random.fold_in(kp, t), 4)
        return (jax.random.randint(k1, (k,), 0, interior, dtype=jnp.int32),
                jax.random.randint(k2, (k,), 0, nbhd, dtype=jnp.int32),
                jax.random.uniform(k3, (k,), dtype=jnp.float32),
                jax.random.uniform(k4, (k,), dtype=jnp.float32))

    cell, dirn, u_act, u_dom = jax.vmap(tile_stream)(
        jnp.arange(n_tiles, dtype=jnp.int32))
    shift = jax.random.randint(ks, (2,), 0, jnp.array([th, tw]),
                               dtype=jnp.int32)
    return escg.sweep(grid, shift, cell, dirn, u_act, u_dom,
                      tile=(th, tw), t_mig=t_mig, t_int=t_int, dom=dom,
                      dtype=dtype)
