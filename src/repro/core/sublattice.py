"""E3: shifted-window synchronous-sublattice engine — the TPU-native redesign
of the paper's maxStep (DESIGN.md §2).

The torus is cut into (th x tw) tiles. Each round:
  1. a uniform random shift (dy, dx) in [0,th) x [0,tw) is applied to the
     torus (``jnp.roll`` — under pjit this moves only edge slivers between
     devices);
  2. every tile runs its K proposals **sequentially** (race-free by
     construction) while all tiles run in parallel; proposal cells are
     restricted to the tile interior (inset 1) so no tile writes outside
     itself — cross-tile conflicts are impossible, no atomics needed;
  3. the shift is rolled back (or accumulated — densities are
     translation-invariant, see the perf log).

Randomizing the sublattice origin each round restores ergodicity (Shim & Amar
2005). This module is the pure-jnp implementation; ``repro.kernels.escg_update``
is the Pallas version with explicit VMEM tiling, validated against
``tile_update`` below.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .lattice import DIRS
from .rng import ProposalBatch
from .rules import apply_pair


def tile_update(tile: jax.Array, props: ProposalBatch, t_eps: jax.Array,
                t_eps_mu: jax.Array, dom: jax.Array) -> jax.Array:
    """Sequentially apply K interior proposals to one (th, tw) tile.

    ``props.cell`` indexes the (th-2)x(tw-2) interior window; the chosen
    neighbour is then always inside the tile for both 4- and 8-neighbourhoods.
    This function is the oracle for the Pallas kernel.
    """
    th, tw = tile.shape
    iw = tw - 2
    dirs = jnp.asarray(DIRS)
    rows = lax.broadcasted_iota(jnp.int32, (th, tw), 0)
    cols = lax.broadcasted_iota(jnp.int32, (th, tw), 1)

    def body(t, p):
        cell, dirn, ua, ud = p
        r = 1 + cell // iw
        c = 1 + cell % iw
        nr = r + dirs[dirn, 0]
        nc = c + dirs[dirn, 1]
        s = t[r, c]
        n = t[nr, nc]
        ns, nn = apply_pair(s, n, ua, ud, t_eps, t_eps_mu, dom)
        # masked selects, not ``t.at[r, c].set``: vmapped over the tiles of
        # a lattice of more than ~5M cells, that scatter returns wrong
        # cells on TPU v5e (jax/libtpu 0.9.0 / 0.0.34); a select cannot
        t = jnp.where((rows == r) & (cols == c), ns, t)
        t = jnp.where((rows == nr) & (cols == nc), nn, t)
        return t, None

    tile, _ = lax.scan(body, tile,
                       (props.cell, props.dirn, props.u_act, props.u_dom))
    return tile


def to_tiles(grid: jax.Array, th: int, tw: int) -> jax.Array:
    """(H, W) -> (T, th, tw), raster tile order."""
    h, w = grid.shape
    return (grid.reshape(h // th, th, w // tw, tw)
                .transpose(0, 2, 1, 3)
                .reshape(-1, th, tw))


def from_tiles(tiles: jax.Array, h: int, w: int) -> jax.Array:
    t, th, tw = tiles.shape
    return (tiles.reshape(h // th, w // tw, th, tw)
                 .transpose(0, 2, 1, 3)
                 .reshape(h, w))


@partial(jax.jit, static_argnames=("tile_shape", "roll_back"))
def run_round(grid: jax.Array, props: ProposalBatch, shift: jax.Array,
              tile_shape: Tuple[int, int], t_eps: jax.Array,
              t_eps_mu: jax.Array, dom: jax.Array,
              roll_back: bool = True) -> jax.Array:
    """One shifted-window round over the whole lattice (pure-jnp engine).

    ``props`` arrays have shape (T, K). The physics point (``t_eps``,
    ``t_eps_mu``, ``dom``) is traced, so one compiled round serves every
    point of a shape. Requires periodic boundaries (the roll assumes a
    torus); reflect boundaries use E1/E2.
    """
    h, w = grid.shape
    th, tw = tile_shape
    g = jnp.roll(grid, (-shift[0], -shift[1]), (0, 1))
    tiles = to_tiles(g, th, tw)
    upd = jax.vmap(lambda t, c, d, ua, ud: tile_update(
        t, ProposalBatch(c, d, ua, ud), t_eps, t_eps_mu, dom))
    tiles = upd(tiles, props.cell, props.dirn, props.u_act, props.u_dom)
    g = from_tiles(tiles, h, w)
    if roll_back:
        g = jnp.roll(g, (shift[0], shift[1]), (0, 1))
    return g
