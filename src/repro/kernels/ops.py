"""Jitted public wrappers around the Pallas kernels.

On a TPU the kernels always compile through Mosaic. Pallas interpret mode
is the CPU path the tests run on (``JAX_PLATFORMS=cpu``), never a
fallback on the chip.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.lattice import DIRS
from ..core.rng import ProposalBatch
from . import density as density_kernel
from . import escg_update as escg_kernel
from . import escg_update_fused as escg_fused_kernel
from . import philox as philox_kernel


def _default_interpret(interpret: Optional[bool] = None) -> bool:
    """Interpret mode exactly when no TPU backs the default device."""
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("tile_shape", "t_eps",
                                             "t_eps_mu", "interpret",
                                             "roll_back"))
def _escg_round_impl(grid, cell, dirn, u_act, u_dom, shift, dom,
                     tile_shape, t_eps, t_eps_mu, interpret, roll_back):
    dirs = jnp.asarray(DIRS, jnp.int32)
    g = jnp.roll(grid, (-shift[0], -shift[1]), (0, 1))
    g = escg_kernel.escg_tile_round(
        g, cell, dirn, u_act, u_dom, jnp.asarray(dom, jnp.float32), dirs,
        tile_shape, t_eps, t_eps_mu, interpret=interpret)
    if roll_back:
        g = jnp.roll(g, (shift[0], shift[1]), (0, 1))
    return g


def escg_round(grid: jax.Array, props: ProposalBatch, shift: jax.Array,
               dom: jax.Array, tile_shape: Tuple[int, int], t_eps: float,
               t_eps_mu: float, roll_back: bool = True) -> jax.Array:
    """Drop-in Pallas replacement for core.sublattice.run_round."""
    return _escg_round_impl(grid, props.cell, props.dirn, props.u_act,
                            props.u_dom, shift, dom, tile_shape,
                            float(t_eps), float(t_eps_mu),
                            _default_interpret(), roll_back)


def philox_bits(n: int, seed: Tuple[int, int] = (0, 0), stream: int = 0,
                block: int = 1024,
                interpret: Optional[bool] = None) -> jax.Array:
    return philox_kernel.philox_bits(n, seed, stream, block,
                                     _default_interpret(interpret))


def philox_uniform(n: int, seed: Tuple[int, int] = (0, 0), stream: int = 0,
                   block: int = 1024,
                   interpret: Optional[bool] = None) -> jax.Array:
    return philox_kernel.philox_uniform(n, seed, stream, block,
                                        _default_interpret(interpret))


def density_counts(grid: jax.Array, species: int,
                   interpret: Optional[bool] = None) -> jax.Array:
    return density_kernel.density_counts(
        grid, species, interpret=_default_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("tile_shape", "k_per_tile",
                                             "t_eps", "t_eps_mu",
                                             "neighbourhood", "interpret",
                                             "roll_back", "grid_tiles_w"))
def _escg_round_fused_impl(grid, seed, round_idx, shift, tile_offset, dom,
                           tile_shape, k_per_tile, t_eps, t_eps_mu,
                           neighbourhood, interpret, roll_back,
                           grid_tiles_w):
    dirs = jnp.asarray(DIRS, jnp.int32)
    g = jnp.roll(grid, (-shift[0], -shift[1]), (0, 1))
    g = escg_fused_kernel.escg_tile_round_fused(
        g, seed, round_idx, jnp.asarray(dom, jnp.float32), dirs,
        tile_shape, k_per_tile, t_eps, t_eps_mu, neighbourhood,
        interpret=interpret, tile_offset=tile_offset,
        grid_tiles_w=grid_tiles_w)
    if roll_back:
        g = jnp.roll(g, (shift[0], shift[1]), (0, 1))
    return g


def escg_round_fused(grid, seed, round_idx, shift, dom, tile_shape,
                     k_per_tile, t_eps, t_eps_mu, neighbourhood=4,
                     roll_back=True, tile_offset=None, grid_tiles_w=None):
    """Fused-PRNG sublattice round: proposals derived in-kernel from Philox
    counters (zero proposal HBM traffic; see escg_update_fused).
    ``tile_offset``/``grid_tiles_w`` key the counters by GLOBAL tile
    identity when ``grid`` is one shard of a larger lattice."""
    if tile_offset is None:
        tile_offset = jnp.zeros((2,), jnp.uint32)
    return _escg_round_fused_impl(grid, seed, round_idx, shift, tile_offset,
                                  dom, tile_shape, k_per_tile, float(t_eps),
                                  float(t_eps_mu), neighbourhood,
                                  _default_interpret(), roll_back,
                                  grid_tiles_w)


@functools.partial(jax.jit, static_argnames=("tile_shape", "k_per_tile",
                                             "t_eps", "t_eps_mu", "species",
                                             "neighbourhood", "interpret",
                                             "grid_tiles_w"))
def _escg_rounds_fused_impl(grid, seeds, shifts, tile_offset, dom,
                            tile_shape, k_per_tile, t_eps, t_eps_mu,
                            species, neighbourhood, interpret,
                            grid_tiles_w):
    dirs = jnp.asarray(DIRS, jnp.int32)
    return escg_fused_kernel.escg_tile_rounds_fused(
        grid, seeds, shifts, jnp.asarray(dom, jnp.float32), dirs,
        tile_shape, k_per_tile, t_eps, t_eps_mu, species, neighbourhood,
        interpret=interpret, tile_offset=tile_offset,
        grid_tiles_w=grid_tiles_w)


def escg_rounds_fused(grid, seeds, shifts, dom, tile_shape, k_per_tile,
                      t_eps, t_eps_mu, species, neighbourhood=4,
                      tile_offset=None, grid_tiles_w=None):
    """K fused MCS in ONE pallas_call (the ``k_mcs`` megakernel): the
    per-step torus shifts are applied in-kernel, so unlike
    ``escg_round_fused`` there is no roll_back knob — the grid comes back
    in the drifted frame of the last step, with per-step species counts
    (K, species + 1) banked alongside (see escg_update_fused)."""
    if tile_offset is None:
        tile_offset = jnp.zeros((2,), jnp.uint32)
    return _escg_rounds_fused_impl(grid, seeds, shifts, tile_offset, dom,
                                   tile_shape, k_per_tile, float(t_eps),
                                   float(t_eps_mu), int(species),
                                   neighbourhood, _default_interpret(),
                                   grid_tiles_w)
