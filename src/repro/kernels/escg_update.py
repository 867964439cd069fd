"""Pallas TPU kernel for the sublattice ESCG round (DESIGN.md §2, E3).

One program = one (th, tw) lattice tile. It plays the tile's K
pre-generated proposals **sequentially** (``fori_loop``) — race-free by
construction. This is the TPU-native replacement for the paper's CUDA
atomics: spatial disjointness instead of per-address arbitration.

Layout (what Mosaic compiles for TPU):
  * the grid is (row bands, tiles per band). The lattice block is the
    whole (th, W) band: a block that spans the full width is legal for
    any W and any th, whereas (8, 16)-style tile blocks are not. The
    band stays resident in VMEM while the band's tiles run one after
    another (the tile axis is ``arbitrary``: consecutive programs revisit
    the same output block).
  * cells are read and written as "load the row at a dynamic sublane
    index, select one lane with a mask, store the row". Mosaic refuses a
    single element at a dynamic lane offset.
  * proposals arrive as (T, K) int32/float32 arrays (the paper's
    pre-generated random-number buffers, T1); each program gets its
    tile's (1, K) slice in SMEM, with the dominance matrix and the
    direction table, so every address is a scalar.
  * int8 lattices: Mosaic loads int8 only as whole blocks, so the band is
    widened to int32 in a VMEM scratch, updated there, and narrowed back
    once per band.

Oracle: ``repro.core.sublattice.tile_update`` (pure jnp). The kernel must
match it bit-for-bit; see tests/test_kernels.py.
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.rules import pair_update

SMEM = pltpu.SMEM


# the whole (small) array in SMEM for every program
SMEM_FULL = pl.BlockSpec(memory_space=SMEM)


def read_cell(ref, r, c):
    """Scalar int32 value of ``ref[r, c]`` (dynamic r and c)."""
    row = ref[pl.ds(r, 1), :]
    lanes = lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return jnp.sum(jnp.where(lanes == c, row, 0))


def write_cell(ref, r, c, v):
    """``ref[r, c] = v`` as a masked whole-row store."""
    row = ref[pl.ds(r, 1), :]
    lanes = lax.broadcasted_iota(jnp.int32, row.shape, 1)
    ref[pl.ds(r, 1), :] = jnp.where(lanes == c, v, row)


def apply_proposal(work_ref, dom_ref, r, c, nr, nc, ua, ud, *,
                   t_eps: float, t_eps_mu: float):
    """One elementary update of the cell at (r, c) against its neighbour at
    (nr, nc), both absolute rows/lanes of the int32 ``work_ref``, by
    ``repro.core.rules.pair_update``. ``dom_ref`` lives in SMEM; every
    operand here is a scalar."""
    s = read_cell(work_ref, r, c)
    n = read_cell(work_ref, nr, nc)
    new_s, new_n = pair_update(s, n, ua, ud, dom_ref[s, n], dom_ref[n, s],
                               t_eps, t_eps_mu)
    write_cell(work_ref, r, c, new_s)
    write_cell(work_ref, nr, nc, new_n)


def apply_tile_proposal(work_ref, dom_ref, dirs_ref, r0, c0, cell, dirn, ua,
                        ud, *, iw: int, t_eps: float, t_eps_mu: float):
    """Proposal ``cell`` (an index into the tile interior, row width
    ``iw``) of the tile whose top-left cell is (r0, c0). Interior cells
    and their neighbours lie inside the tile, so nothing wraps."""
    r = r0 + 1 + cell // iw
    c = c0 + 1 + cell % iw
    apply_proposal(work_ref, dom_ref, r, c, r + dirs_ref[dirn, 0],
                   c + dirs_ref[dirn, 1], ua, ud, t_eps=t_eps,
                   t_eps_mu=t_eps_mu)


def band_layout(h: int, w: int, tile_shape: Tuple[int, int]):
    """(rows of one lattice block, tiles per block). A block holds the
    fewest whole tile rows that make a multiple of 8 sublanes and divide
    ``h``, else the whole lattice (a block dim is legal when it is a
    multiple of 8 or the array's own), and always the full width."""
    th, tw = tile_shape
    bh = th * 8 // math.gcd(th, 8)
    if h % bh:
        bh = h
    return bh, (bh // th) * (w // tw)


def band_tile(th: int, tw: int, tiles_w: int):
    """(tile row within the band, tile column, r0, c0) of this program:
    program (i, j) runs tile j, in raster order, of band i."""
    j = pl.program_id(1)
    q = j // tiles_w
    tj = j % tiles_w
    return q, tj, q * th, tj * tw


def band_program(grid_ref, out_ref, scratch, sweep):
    """Run ``sweep(work_ref)`` for this program's tile on the band block.

    The first tile of a band loads the band into the int32 working
    buffer; the last one writes it back. For int32 lattices the working
    buffer is the output block itself."""
    j = pl.program_id(1)
    work = scratch[0] if scratch else out_ref

    @pl.when(j == 0)
    def _():
        work[...] = grid_ref[...].astype(jnp.int32)

    sweep(work)

    if scratch:
        @pl.when(j == pl.num_programs(1) - 1)
        def _():
            out_ref[...] = work[...].astype(out_ref.dtype)


def band_call(kernel, grid: jax.Array, tile_shape: Tuple[int, int],
              scalar_specs, interpret: bool, name: str):
    """``pallas_call`` over (row band, tile) programs with the lattice in
    VMEM as ``band_layout`` blocks; ``scalar_specs`` are the SMEM operands
    that precede the lattice. ``kernel`` takes the static ``band_tiles``
    (tiles per band) keyword. ``name`` is the kernel's stable name, which
    the compiled op and the profiler's trace carry."""
    h, w = grid.shape
    bh, band_tiles = band_layout(h, w, tile_shape)
    band = pl.BlockSpec((bh, w), lambda i, j: (i, 0))
    scratch = ([] if grid.dtype == jnp.int32
               else [pltpu.VMEM((bh, w), jnp.int32)])
    return pl.pallas_call(
        functools.partial(kernel, band_tiles=band_tiles),
        grid=(h // bh, band_tiles),
        in_specs=[*scalar_specs, band],
        out_specs=band,
        out_shape=jax.ShapeDtypeStruct((h, w), grid.dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )


def _kernel(cell_ref, dirn_ref, uact_ref, udom_ref, dom_ref, dirs_ref,
            grid_ref, out_ref, *scratch, t_eps: float, t_eps_mu: float,
            k: int, th: int, tw: int, tiles_w: int, band_tiles: int):
    _, _, r0, c0 = band_tile(th, tw, tiles_w)
    # raster tile id mod 8: this tile's row of the (8, K) proposal block
    p = (pl.program_id(0) * band_tiles + pl.program_id(1)) % 8

    def sweep(work):
        def body(jj, _):
            apply_tile_proposal(
                work, dom_ref, dirs_ref, r0, c0, cell_ref[p, jj],
                dirn_ref[p, jj], uact_ref[p, jj], udom_ref[p, jj],
                iw=tw - 2, t_eps=t_eps, t_eps_mu=t_eps_mu)
            return 0

        lax.fori_loop(0, k, body, 0)

    band_program(grid_ref, out_ref, scratch, sweep)


def escg_tile_round(grid: jax.Array, cell: jax.Array, dirn: jax.Array,
                    u_act: jax.Array, u_dom: jax.Array, dom: jax.Array,
                    dirs: jax.Array, tile_shape: Tuple[int, int],
                    t_eps: float, t_eps_mu: float,
                    interpret: bool = False) -> jax.Array:
    """Run one sublattice round over an already-shifted (H, W) grid.

    cell/dirn/u_act/u_dom: (T, K) proposal arrays in raster tile order.
    dirs: (8, 2) int32 direction table. Returns the updated grid.
    """
    h, w = grid.shape
    th, tw = tile_shape
    t, k = cell.shape
    assert t == (h // th) * (w // tw), (t, h, w, tile_shape)

    kern = functools.partial(_kernel, t_eps=float(t_eps),
                             t_eps_mu=float(t_eps_mu), k=int(k), th=th,
                             tw=tw, tiles_w=w // tw)
    # the proposals of 8 consecutive tiles per block: (1, K) is not a
    # legal block shape, (8, K) is
    _, band_tiles = band_layout(h, w, tile_shape)
    prop = pl.BlockSpec((8, k), lambda i, j: ((i * band_tiles + j) // 8, 0),
                        memory_space=SMEM)
    call = band_call(kern, grid, tile_shape,
                     [prop, prop, prop, prop, SMEM_FULL, SMEM_FULL],
                     interpret, "escg_update")
    return call(cell, dirn, u_act, u_dom, dom, dirs, grid)
