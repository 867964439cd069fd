"""Compile every Pallas kernel of the main path for a TPU v5e, without one.

The TPU compiler is installed with JAX and compiles for a chip that is
described (``topologies.get_topology_desc``) rather than attached. Interpret
mode, which the other kernel tests run in, cannot see what Mosaic refuses:
unaligned dynamic slices, illegal block shapes, int8 vector loads, VMEM
overflow. Each case here compiles at deployment size (3200x3200, the
paper's largest lattice) and asserts that the compiled program holds the
kernel (``tpu_custom_call``), i.e. that nothing fell back to XLA.

The topology is described inside a fixture, never at import time: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import EscgParams
from repro.core.engines import _tiled_setup
from repro.core.lattice import DIRS
from repro.kernels import escg_update, escg_update_fused

L = 3200                         # the paper's largest lattice (Fig 4.3)
TILE = EscgParams().tile         # the engines' default tile
SPECIES = 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_for_chip(fn, *args, kernel):
    """Compile ``fn`` for the chip; the kernel must reach Mosaic as a
    custom call that carries its stable name (under ``vmap`` with a
    ``vmap_`` prefix), which the profiler's trace then shows."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "the kernel did not reach Mosaic"
    assert re.search(rf"%\w*{kernel}\w*(\.\d+)? = .* custom-call\(",
                     text), \
        f"no custom call named {kernel!r}"


def _setup(h, w, tile=TILE):
    p = EscgParams(height=h, length=w, tile=tile)
    th, tw, n_tiles, k_per_tile, _ = _tiled_setup(p)
    return n_tiles, k_per_tile


def _dom(sharding):
    return jax.ShapeDtypeStruct((SPECIES + 1, SPECIES + 1), jnp.float32,
                                sharding=sharding)


@pytest.mark.parametrize("cell_dtype", ["int32", "int8"])
def test_plain_kernel_compiles_at_3200(one_chip, no_compile_cache,
                                       cell_dtype):
    n_tiles, k = _setup(L, L)

    def round_(grid, cell, dirn, ua, ud, dom):
        return escg_update.escg_tile_round(
            grid, cell, dirn, ua, ud, dom, jnp.asarray(DIRS, jnp.int32),
            TILE, 0.25, 0.6, interpret=False)

    prop = lambda dt: jax.ShapeDtypeStruct((n_tiles, k), dt,
                                           sharding=one_chip)
    _compile_for_chip(
        round_,
        jax.ShapeDtypeStruct((L, L), jnp.dtype(cell_dtype),
                             sharding=one_chip),
        prop(jnp.int32), prop(jnp.int32), prop(jnp.float32),
        prop(jnp.float32), _dom(one_chip), kernel="escg_update")


@pytest.mark.parametrize("cell_dtype", ["int32", "int8"])
def test_fused_kernel_compiles_at_3200(one_chip, no_compile_cache,
                                       cell_dtype):
    _, k = _setup(L, L)

    def round_(grid, seed, dom):
        return escg_update_fused.escg_tile_round_fused(
            grid, seed, jnp.uint32(0), dom, jnp.asarray(DIRS, jnp.int32),
            TILE, k, 0.25, 0.6, 4, interpret=False)

    _compile_for_chip(
        round_,
        jax.ShapeDtypeStruct((L, L), jnp.dtype(cell_dtype),
                             sharding=one_chip),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip),
        _dom(one_chip), kernel="escg_round_fused")


def test_fused_kernel_compiles_at_3200_at_its_widest(one_chip,
                                                      no_compile_cache):
    """Eight neighbours and a 9 x 9 dominance table (8 species): the
    kernel's direction select chain and dominance lookup at their
    largest, over the 3200x3200 lattice's lane blocks."""
    _, k = _setup(L, L)
    species = 8

    def round_(grid, seed, dom):
        return escg_update_fused.escg_tile_round_fused(
            grid, seed, jnp.uint32(0), dom, jnp.asarray(DIRS, jnp.int32),
            TILE, k, 0.25, 0.6, 8, interpret=False)

    _compile_for_chip(
        round_,
        jax.ShapeDtypeStruct((L, L), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip),
        jax.ShapeDtypeStruct((species + 1, species + 1), jnp.float32,
                             sharding=one_chip),
        kernel="escg_round_fused")


def test_fused_kernel_compiles_for_a_trial_batch(one_chip,
                                                 no_compile_cache):
    """The trial drivers vmap the kernel: every operand gains a batch
    axis, and each block shape must stay legal with it."""
    h = w = 200
    tile = (8, 25)                   # a tile that divides L=200
    _, k = _setup(h, w, tile)

    def round_(grid, seed, dom):
        return escg_update_fused.escg_tile_round_fused(
            grid, seed, jnp.uint32(0), dom, jnp.asarray(DIRS, jnp.int32),
            tile, k, 0.25, 0.6, 4, interpret=False)

    _compile_for_chip(
        jax.vmap(round_, in_axes=(0, 0, None)),
        jax.ShapeDtypeStruct((16, h, w), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((16, 2), jnp.uint32, sharding=one_chip),
        _dom(one_chip), kernel="escg_round_fused")


def _largest_accepted_side(tw: int) -> int:
    """Largest square int32 lattice, a multiple of the tile width, that
    ``check_mega_fits`` accepts."""
    side = math.isqrt(escg_update_fused.MEGA_LATTICE_BUDGET_BYTES // 8)
    return side - side % tw


def _mega(one_chip, side):
    _, k = _setup(side, side)
    steps = 2

    def rounds(grid, seeds, shifts, dom):
        return escg_update_fused.escg_tile_rounds_fused(
            grid, seeds, shifts, dom, jnp.asarray(DIRS, jnp.int32), TILE, k,
            0.25, 0.6, SPECIES, 4, interpret=False)

    _compile_for_chip(
        rounds,
        jax.ShapeDtypeStruct((side, side), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((steps, 2), jnp.uint32, sharding=one_chip),
        jax.ShapeDtypeStruct((steps, 2), jnp.int32, sharding=one_chip),
        _dom(one_chip), kernel="escg_rounds_fused")


def test_megakernel_compiles_at_its_largest_accepted_size(
        one_chip, no_compile_cache):
    side = _largest_accepted_side(TILE[1])
    EscgParams(height=side, length=side, engine="pallas_fused",
               k_mcs=2).validate()
    _mega(one_chip, side)


def test_megakernel_refuses_the_next_size_by_its_vmem_limit(
        one_chip, no_compile_cache):
    side = _largest_accepted_side(TILE[1]) + TILE[1]
    with pytest.raises(ValueError, match="VMEM limit"):
        EscgParams(height=side, length=side, engine="pallas_fused",
                   k_mcs=2).validate()
    with pytest.raises(ValueError, match="VMEM limit"):
        _mega(one_chip, side)
