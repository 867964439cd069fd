"""Elementary-step semantics (paper Algorithm 3.2) as a pure pair update.

This module is the single source of truth for the game rules. Every engine
(sequential reference, batched maxStep port, sublattice engine, Pallas kernel)
applies exactly this function to the (cell, neighbour) pair, so engine
equivalence reduces to scheduling equivalence.

Given cell species ``s``, neighbour species ``n``, an action draw
``u_act ~ U[0,1)`` and a dominance draw ``u_dom ~ U[0,1)``:

    if s == n:                      no-op            (paper: skip same species)
    elif u_act < t_eps:             migration        (swap)
    elif u_act < t_eps_mu:          interaction      (probabilistic dominance)
    else:                           reproduction     (fill the empty site)

Interaction uses the padded dominance matrix D (row/col 0 = empty = all
zeros): with p1 = D[s, n], p2 = D[n, s],
    u_dom <  p1        -> neighbour dies
    u_dom <  p1 + p2   -> cell dies
which reproduces the paper's deterministic ``dominates()`` branch when
p ∈ {0,1} and Park et al.'s probabilistic rates otherwise. Emptiness guards
(interaction needs both non-empty; reproduction needs exactly one empty) are
implied by the zero padding and the s != n precondition.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

_F32_TINY = float(np.finfo(np.float32).tiny)


def quantize_rate(x: float) -> float:
    """``x`` as the device holds it: rounded to float32, with subnormals
    flushed to zero (XLA on CPU and TPU flushes them in every comparison).
    The one place host-side rates and thresholds become device numbers."""
    x = float(np.float32(x))
    return 0.0 if abs(x) < _F32_TINY else x


def apply_pair(s: jax.Array, n: jax.Array, u_act: jax.Array,
               u_dom: jax.Array, t_eps: float, t_eps_mu: float,
               dom: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Vectorized pure pair update. All args broadcastable; returns the new
    pair in the input cell dtype (int8 lattices supported)."""
    cell_dt = s.dtype
    s = s.astype(jnp.int32)
    n = n.astype(jnp.int32)
    new_s, new_n = pair_update(s, n, u_act, u_dom, dom[s, n], dom[n, s],
                               t_eps, t_eps_mu)
    return new_s.astype(cell_dt), new_n.astype(cell_dt)


def pair_update(s: jax.Array, n: jax.Array, u_act: jax.Array,
                u_dom: jax.Array, p1: jax.Array, p2: jax.Array,
                t_eps: float, t_eps_mu: float
                ) -> Tuple[jax.Array, jax.Array]:
    """``apply_pair`` on int32 species with the dominance entries already
    looked up: p1 = D[s, n], p2 = D[n, s]. Kernels that cannot gather
    from D (scalar SMEM reads, one-hot lookups per lane) call this."""
    same = s == n

    migrate = u_act < t_eps
    interact = (u_act >= t_eps) & (u_act < t_eps_mu)
    reproduce = u_act >= t_eps_mu

    kill_n = interact & (u_dom < p1)
    kill_s = interact & ~kill_n & (u_dom < p1 + p2)

    rep_to_n = reproduce & (n == 0)     # s != n ensures s != 0 here
    rep_to_s = reproduce & (s == 0)

    zero = jnp.zeros_like(s)
    new_s = jnp.where(migrate, n,
            jnp.where(kill_s, zero,
            jnp.where(rep_to_s, n, s)))
    new_n = jnp.where(migrate, s,
            jnp.where(kill_n, zero,
            jnp.where(rep_to_n, s, n)))

    new_s = jnp.where(same, s, new_s)
    new_n = jnp.where(same, n, new_n)
    return new_s, new_n


def apply_pair_reference(s: int, n: int, u_act: float, u_dom: float,
                         t_eps: float, t_eps_mu: float, dom) -> Tuple[int, int]:
    """Plain-Python transliteration of paper Algorithm 3.2 (test oracle).
    Every real number is first made the float32 the device compares
    (``quantize_rate``)."""
    u_act, u_dom, t_eps, t_eps_mu = (quantize_rate(v) for v in
                                     (u_act, u_dom, t_eps, t_eps_mu))
    if s == n:
        return s, n
    if u_act < t_eps:                       # migration
        return n, s
    if u_act < t_eps_mu:                    # interaction
        p1 = quantize_rate(dom[s, n])
        p2 = quantize_rate(dom[n, s])
        if u_dom < p1:
            return s, 0                     # neighbour dies
        if u_dom < quantize_rate(p1 + p2):
            return 0, n                     # self dies
        return s, n
    # reproduction
    if n == 0:
        return s, s
    if s == 0:
        return n, n
    return s, n
