"""Reference proposal streams of the ``pallas_fused`` engine: Philox-4x32-10
counters derived per proposal (Salmon et al., "Parallel random numbers:
as easy as 1, 2, 3", SC 2011).

Per Monte-Carlo step with the two-word key ``k``: the Philox key is ``k``
itself; proposal j of raster tile t has the counter ``(t * K + j, 0, 0,
0)`` and its four output words become (interior cell = x0 mod interior,
direction = x1 mod neighbourhood, action draw = top 24 bits of x2 times
2^-24, dominance draw = the same of x3). The torus shift is
``randint(fold_in(k, 1), (2,), 0, (th, tw))``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import escg

MUL = (0xD2511F53, 0xCD9E8D57)     # Philox-4x32 round multipliers
WEYL = (0x9E3779B9, 0xBB67AE85)    # key schedule increments


def _mul_hi_lo(a, b: int):
    """High and low words of the 64-bit product of uint32 ``a`` and the
    constant ``b``, from four 16 x 16-bit partial products."""
    a_lo, a_hi = a & 0xFFFF, a >> 16
    b_lo, b_hi = jnp.uint32(b & 0xFFFF), jnp.uint32(b >> 16)
    ll, lh = a_lo * b_lo, a_lo * b_hi
    hl, hh = a_hi * b_lo, a_hi * b_hi
    mid = (ll >> 16) + (lh & 0xFFFF) + (hl & 0xFFFF)
    hi = hh + (lh >> 16) + (hl >> 16) + (mid >> 16)
    lo = (mid << 16) | (ll & 0xFFFF)
    return hi, lo


def philox4x32(c0, c1, c2, c3, k0, k1):
    """Philox-4x32-10 of the counter (c0..c3) under the key (k0, k1)."""
    c = [jnp.asarray(x, jnp.uint32) for x in (c0, c1, c2, c3)]
    k0, k1 = jnp.uint32(k0), jnp.uint32(k1)
    for r in range(10):
        hi0, lo0 = _mul_hi_lo(c[0], MUL[0])
        hi1, lo1 = _mul_hi_lo(c[2], MUL[1])
        c = [hi1 ^ c[1] ^ k0, lo1, hi0 ^ c[3] ^ k1, lo0]
        k0 = k0 + jnp.uint32(WEYL[0])
        k1 = k1 + jnp.uint32(WEYL[1])
    return c


def _unit(x):
    return (x >> 8).astype(jnp.int32).astype(jnp.float32) * np.float32(
        2.0 ** -24)


def step(grid, key, cfg, *, t_mig, t_int, dom, dtype=jnp.float32):
    """One Monte-Carlo step of one lattice under the step key ``key``."""
    th, tw = cfg["tile"]
    n_tiles, k, interior = escg.tiling(cfg)
    words = jnp.asarray(key, jnp.uint32).reshape(-1)
    idx = jnp.arange(n_tiles * k, dtype=jnp.uint32)
    zero = jnp.zeros_like(idx)
    x0, x1, x2, x3 = philox4x32(idx, zero, zero, zero, words[0], words[1])
    cell = (x0 % jnp.uint32(interior)).astype(jnp.int32)
    dirn = (x1 % jnp.uint32(cfg["neighbourhood"])).astype(jnp.int32)
    shape = (n_tiles, k)
    shift = jax.random.randint(jax.random.fold_in(key, 1), (2,), 0,
                               jnp.array([th, tw]), dtype=jnp.int32)
    return escg.sweep(grid, shift, cell.reshape(shape), dirn.reshape(shape),
                      _unit(x2).reshape(shape), _unit(x3).reshape(shape),
                      tile=(th, tw), t_mig=t_mig, t_int=t_int, dom=dom,
                      dtype=dtype)
