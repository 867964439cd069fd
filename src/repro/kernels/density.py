"""Pallas density/histogram kernel (paper §3.2.2, densities.metal).

The paper offloads per-MCS density counting to the GPU with an atomic
species-count array. TPU adaptation: a sequential-grid reduction — each
program one-hot-counts its VMEM block and accumulates into a single output
block (Pallas TPU grids execute in order, so the ``program_id == 0`` init +
accumulate pattern replaces atomics).

Oracle: ``jnp.bincount`` (repro.kernels.ref.density_ref).

:func:`density_counts_sharded` lifts the kernel into a ``shard_map``
region: each device one-hot-counts its local block and the partials are
``psum``med into global counts — the observable pipeline's count path on
domain-decomposed lattices (DESIGN.md §11).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax import shard_map
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P


def _kernel(grid_ref, out_ref, *, n_labels: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    block = grid_ref[...]
    labels = jax.lax.iota(jnp.int32, n_labels).reshape(1, 1, n_labels)
    onehot = (block[:, :, None] == labels).astype(jnp.int32)
    out_ref[0, :] += jnp.sum(onehot, axis=(0, 1))


def density_counts(grid: jax.Array, species: int, block_rows: int = 8,
                   interpret: bool = False) -> jax.Array:
    """Counts per label 0..S over an (H, W) int32 grid."""
    h, w = grid.shape
    if h % block_rows:
        block_rows = 1
    n_labels = species + 1
    kern = functools.partial(_kernel, n_labels=n_labels)
    out = pl.pallas_call(
        kern,
        grid=(h // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, w), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, n_labels), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, n_labels), jnp.int32),
        interpret=interpret,
    )(grid)
    return out[0]


def density_counts_sharded(grid: jax.Array, species: int, mesh: Mesh,
                           row_axis: str = "rows", col_axis: str = "cols",
                           block_rows: int = 8,
                           interpret: bool = False) -> jax.Array:
    """Global label counts of a lattice sharded P(row_axis, col_axis).

    Runs :func:`density_counts` per shard inside a ``shard_map`` region
    and all-reduces the per-device partial histograms with ``lax.psum`` —
    no device ever materializes a remote block. Bit-identical to
    ``density_counts`` (and to the ``density_ref`` bincount oracle) on
    the gathered lattice: one-hot integer sums are order-independent.
    """
    def local_counts(gl):
        part = density_counts(gl, species, block_rows=block_rows,
                              interpret=interpret)
        return jax.lax.psum(part, (row_axis, col_axis))

    return shard_map(local_counts, mesh=mesh,
                     in_specs=P(row_axis, col_axis), out_specs=P(),
                     check_vma=False)(grid)
