"""Plain reference of the ESCG semantics the benchmark's cells run.

Written from the published description and nothing else: the lattice and
its uniform initial state (arXiv:2508.16639 §3.1.1), the elementary step
(Algorithm 3.2), and the shifted-window sweep in which every tile plays
its proposals one after another while tiles are independent (§4.2.4 as
redesigned for tiles: interior cells only, a uniform torus shift per
Monte-Carlo step, never rolled back). The proposal streams that feed the
sweep differ by engine and live beside this file, one module per engine.

Nothing here imports the code under test. Every real number is compared
in ``dtype``: float32 is what the configurations state; the control runs
the same reference with ``dtype=jnp.bfloat16``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# neighbour offsets (row, col): up, down, left, right, then the diagonals
DIRECTIONS = np.array([(-1, 0), (1, 0), (0, -1), (0, 1),
                       (-1, -1), (-1, 1), (1, -1), (1, 1)], np.int32)


def dominance_matrix(cfg: dict) -> np.ndarray:
    """(S+1, S+1) float32 kill rates from the configuration's explicit
    ``dominance`` edges ``[winner, loser, rate]`` (species 1..S; row and
    column 0 are the empty site, which kills and dies of nothing)."""
    s = cfg["species"]
    d = np.zeros((s + 1, s + 1), np.float32)
    for winner, loser, rate in cfg["dominance"]:
        d[winner, loser] = max(d[winner, loser], np.float32(rate))
    return d


def thresholds(cfg: dict) -> tuple:
    """Cumulative action thresholds (migration, migration + interaction)
    on u ~ U[0, 1), as float32: epsilon = 2 M N unless the configuration
    fixes it, normalised by epsilon + mu + sigma."""
    n = cfg["height"] * cfg["length"]
    eps = cfg["epsilon"]
    if eps is None:
        eps = 2.0 * cfg["mobility"] * n
    total = eps + cfg["mu"] + cfg["sigma"]
    return np.float32(eps / total), np.float32((eps + cfg["mu"]) / total)


def init_lattice(key, height: int, width: int, species: int,
                 empty: float) -> jax.Array:
    """Each cell empty with probability ``empty``, else a species drawn
    uniformly from 1..S (two threefry draws from the two halves of
    ``key``)."""
    k_occ, k_lab = jax.random.split(key)
    occupied = jax.random.uniform(k_occ, (height, width)) >= empty
    labels = jax.random.randint(k_lab, (height, width), 1, species + 1,
                                dtype=jnp.int32)
    return jnp.where(occupied, labels, 0)


def counts(grid: jax.Array, species: int) -> jax.Array:
    """Cells per label 0..S over the last two axes."""
    labels = jnp.arange(species + 1, dtype=grid.dtype)
    return jnp.sum(grid[..., None] == labels, axis=(-3, -2),
                   dtype=jnp.int32)


def unlike_bonds(grid: jax.Array) -> jax.Array:
    """Nearest-neighbour bonds on the torus whose two cells differ, each
    bond counted once (to the right and downward), as an int32 count."""
    right = jnp.sum(grid != jnp.roll(grid, -1, axis=-1), dtype=jnp.int32)
    down = jnp.sum(grid != jnp.roll(grid, -1, axis=-2), dtype=jnp.int32)
    return right + down


def elementary_step(s, n, u_act, u_dom, t_mig, t_int, dom):
    """Algorithm 3.2 on a (cell, neighbour) pair of species ``s``, ``n``.

    Same species: nothing. Otherwise, by ``u_act``: below ``t_mig`` the
    two swap; below ``t_int`` they interact (the neighbour dies if
    ``u_dom < D[s, n]``, else the cell dies if ``u_dom < D[s, n] + D[n,
    s]``); above it an empty one of the two is filled by the other."""
    differ = s != n
    migrate = differ & (u_act < t_mig)
    interact = differ & ~migrate & (u_act < t_int)
    reproduce = differ & ~migrate & ~interact
    p_sn = dom[s, n]
    p_ns = dom[n, s]
    n_dies = interact & (u_dom < p_sn)
    s_dies = interact & ~n_dies & (u_dom < p_sn + p_ns)
    new_s = jnp.select([migrate, s_dies, reproduce & (s == 0)],
                       [n, jnp.zeros_like(s), n], s)
    new_n = jnp.select([migrate, n_dies, reproduce & (n == 0)],
                       [s, jnp.zeros_like(n), s], n)
    return new_s, new_n


def to_tiles(grid, th: int, tw: int):
    """(..., H, W) -> (..., T, th * tw), tiles in raster order."""
    *lead, h, w = grid.shape
    g = grid.reshape(*lead, h // th, th, w // tw, tw)
    g = jnp.swapaxes(g, -3, -2)
    return g.reshape(*lead, (h // th) * (w // tw), th * tw)


def from_tiles(tiles, h: int, w: int, th: int, tw: int):
    *lead, _, _ = tiles.shape
    g = tiles.reshape(*lead, h // th, w // tw, th, tw)
    g = jnp.swapaxes(g, -3, -2)
    return g.reshape(*lead, h, w)


def sweep(grid, shift, cell, dirn, u_act, u_dom, *, tile, t_mig, t_int,
          dom, dtype=jnp.float32):
    """One Monte-Carlo step of the shifted-window sweep.

    ``grid`` (H, W) is rolled by ``-shift`` and cut into (th, tw) tiles;
    tile t plays proposals ``[t, 0..K)`` in order, proposal j naming an
    interior cell ``cell[t, j]`` (row-major in the (th-2) x (tw-2)
    interior) and a direction. All tiles run side by side, since an
    interior cell and its neighbour never leave their tile. The result
    stays in the rolled frame."""
    h, w = grid.shape
    th, tw = tile
    iw = tw - 2
    tiles = to_tiles(jnp.roll(grid, (-shift[0], -shift[1]), (0, 1)),
                     th, tw).astype(jnp.int32)
    pos = jnp.arange(th * tw, dtype=jnp.int32)[None, :]
    rows_of = jnp.asarray(DIRECTIONS[:, 0])
    cols_of = jnp.asarray(DIRECTIONS[:, 1])
    dom = jnp.asarray(dom).astype(dtype)
    t_mig = jnp.asarray(t_mig, jnp.float32).astype(dtype)
    t_int = jnp.asarray(t_int, jnp.float32).astype(dtype)

    def play(tiles, prop):
        c, d, ua, ud = prop                      # each (T,)
        r = 1 + c // iw
        col = 1 + c % iw
        here = r * tw + col
        there = (r + rows_of[d]) * tw + col + cols_of[d]
        s = jnp.take_along_axis(tiles, here[:, None], 1)[:, 0]
        n = jnp.take_along_axis(tiles, there[:, None], 1)[:, 0]
        new_s, new_n = elementary_step(s, n, ua.astype(dtype),
                                       ud.astype(dtype), t_mig, t_int, dom)
        tiles = jnp.where(pos == here[:, None], new_s[:, None], tiles)
        tiles = jnp.where(pos == there[:, None], new_n[:, None], tiles)
        return tiles, None

    props = tuple(jnp.swapaxes(a, 0, 1) for a in (cell, dirn, u_act, u_dom))
    tiles, _ = jax.lax.scan(play, tiles, props)
    return from_tiles(tiles, h, w, th, tw)


def tiling(cfg: dict) -> tuple:
    """(tiles, proposals per tile, interior cells per tile) of a
    configuration: one Monte-Carlo step proposes at least N updates,
    spread evenly over the tiles."""
    th, tw = cfg["tile"]
    n_tiles = (cfg["height"] // th) * (cfg["length"] // tw)
    n = cfg["height"] * cfg["length"]
    return n_tiles, -(-n // n_tiles), (th - 2) * (tw - 2)
