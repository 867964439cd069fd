"""Fused-PRNG sublattice kernel (§Perf H3 iter-2, beyond-paper).

The paper pre-generates random-number buffers in device memory and tunes
their size (--numRandoms, Fig 4.2). This kernel ELIMINATES that traffic and
the tuning knob: each tile derives its proposals from Philox-4x32 counters
*inside* the kernel, in VMEM, at the moment of consumption — 16 bytes per
elementary update of HBM traffic (4 random words) drop to zero; what
remains is the grid itself.

Counter layout (``kernels.philox.philox_proposal_fields``): c0 = global
tile_id * K + j (proposal index), c1 = round index, c2 = c3 = 0; key = two
words derived from the simulation PRNG key per MCS. Uniform ints via
modulus (the paper's own technique, §3.2.1): for a 32-bit word reduced
mod m the bias is at most m / 2^32, i.e. max(interior, nbhd) / 2^32 here
— e.g. < 2^-25 for the default 8x16 tile (interior 84), and < 2^-22 only
while interior < 2^10. ``check_counter_capacity`` guards the other edge:
c0 = tile_id * K + j must not wrap uint32, or distant tiles would
silently alias each other's streams.

**Global tile identity.** ``tile_offset``/``grid_tiles_w`` let a shard of
a domain-decomposed lattice derive the SAME counters the single-device
kernel would: the program's (i, j) position is offset by the shard's
first owned tile and raster-flattened against the GLOBAL tile-grid width.
That is the whole multi-device contract — the sharded engines'
``local_kernel='fused'`` path stays bit-identical to ``pallas_fused`` for
every mesh factorization while no proposal array ever touches HBM
(DESIGN.md §6).

Oracle: host-side Philox (kernels.ref.philox4x32_ref) feeding the standard
tile oracle — bit-exact match required (tests/test_kernels.py).
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .escg_update import (SMEM_FULL, apply_proposal, apply_tile_proposal,
                          band_call, band_program, band_tile)
from .philox import philox_proposal_fields


def check_counter_capacity(n_tiles: int, k_per_tile: int) -> None:
    """Guard the c0 counter word: ``tile_id * k_per_tile + j`` is computed
    in uint32, so the GLOBAL proposal-index space must fit in 2^32 or
    distant tiles silently alias each other's Philox streams. A 3200x3200
    lattice of 8x16 tiles (80_000 tiles, k~128) uses ~10^7 counters —
    comfortably inside; the wrap point is real for k_per_tile blowups."""
    if n_tiles * k_per_tile > 2 ** 32:
        raise ValueError(
            f"fused-Philox counter overflow: {n_tiles} global tiles x "
            f"{k_per_tile} proposals/tile = {n_tiles * k_per_tile} counters "
            f"exceeds the uint32 counter space (2^32); shrink k_per_tile "
            f"or enlarge the tile")


def _tile_proposals(tile_id, round_idx, k0, k1, *, k: int, interior: int,
                    nbhd: int):
    """This tile's K proposals as four (1, K) vectors, from Philox counters
    ``tile_id * K + j``."""
    j = lax.broadcasted_iota(jnp.int32, (1, k), 1)
    idx = tile_id * jnp.uint32(k) + j.astype(jnp.uint32)
    return j, philox_proposal_fields(idx, round_idx, k0, k1, interior, nbhd)


def _pick(vec, lanes, jj):
    """Scalar ``vec[0, jj]``: a masked sum, since Mosaic reads no single
    lane of a vector at a dynamic offset."""
    return jnp.sum(jnp.where(lanes == jj, vec, jnp.zeros_like(vec)))


def _kernel(seed_ref, round_ref, off_ref, dom_ref, dirs_ref, grid_ref,
            out_ref, *scratch, t_eps: float, t_eps_mu: float, k: int,
            th: int, tw: int, tiles_w: int, interior: int, nbhd: int,
            gw: int, band_tiles: int):
    q, tj, r0, c0 = band_tile(th, tw, tiles_w)
    # global raster tile id: the program's tile offset by this shard's
    # first owned tile, flattened against the GLOBAL tile-grid width
    ti = pl.program_id(0) * (band_tiles // tiles_w) + q
    tile_id = ((off_ref[0, 0] + ti).astype(jnp.uint32) * jnp.uint32(gw)
               + (off_ref[0, 1] + tj).astype(jnp.uint32))

    def sweep(work):
        lanes, (cells, dirns, uact, udom) = _tile_proposals(
            tile_id, round_ref[0, 0], seed_ref[0, 0], seed_ref[0, 1], k=k,
            interior=interior, nbhd=nbhd)

        def body(jj, _):
            apply_tile_proposal(
                work, dom_ref, dirs_ref, r0, c0, _pick(cells, lanes, jj),
                _pick(dirns, lanes, jj), _pick(uact, lanes, jj),
                _pick(udom, lanes, jj), iw=tw - 2, t_eps=t_eps,
                t_eps_mu=t_eps_mu)
            return 0

        lax.fori_loop(0, k, body, 0)

    band_program(grid_ref, out_ref, scratch, sweep)


def escg_tile_round_fused(grid: jax.Array, seed: jax.Array,
                          round_idx: jax.Array, dom: jax.Array,
                          dirs: jax.Array, tile_shape: Tuple[int, int],
                          k_per_tile: int, t_eps: float, t_eps_mu: float,
                          neighbourhood: int = 4,
                          interpret: bool = False,
                          tile_offset: Optional[jax.Array] = None,
                          grid_tiles_w: Optional[int] = None) -> jax.Array:
    """One fused round over an already-shifted (H, W) grid.

    seed: (2,) uint32 key words; round_idx: scalar uint32.

    ``grid`` may be a SHARD of a larger lattice: ``tile_offset`` is this
    shard's (row, col) position in global tile units and ``grid_tiles_w``
    the global tile-grid width, so in-kernel counters stay keyed by global
    tile identity (defaults — zero offset, local width — recover the
    single-device kernel exactly).
    """
    h, w = grid.shape
    th, tw = tile_shape
    gh, gw = h // th, w // tw
    if grid_tiles_w is None:
        # single-lattice call: the local tile grid IS the global one.
        # Sharded callers pass grid_tiles_w and guard with the true
        # global tile count themselves (core/sharded.py).
        check_counter_capacity(gh * gw, k_per_tile)

    kern = functools.partial(
        _kernel, t_eps=float(t_eps), t_eps_mu=float(t_eps_mu),
        k=int(k_per_tile), th=th, tw=tw, tiles_w=gw,
        interior=(th - 2) * (tw - 2), nbhd=int(neighbourhood),
        gw=int(gw if grid_tiles_w is None else grid_tiles_w))
    if tile_offset is None:
        tile_offset = jnp.zeros((2,), jnp.uint32)
    call = band_call(kern, grid, tile_shape, [SMEM_FULL] * 5, interpret,
                     "escg_round_fused")
    # scalar operands stay 2-D: under vmap a batched 1-D operand would get
    # an illegal (1, n) block
    return call(seed.reshape(1, 2).astype(jnp.uint32),
                jnp.reshape(round_idx, (1, 1)).astype(jnp.uint32),
                jnp.reshape(tile_offset, (1, 2)).astype(jnp.int32),
                dom, dirs, grid)


# ------------------------ multi-MCS megakernel ---------------------------- #

# Mosaic's scoped-VMEM cap for the megakernel (v5e has 128 MiB of VMEM
# per core), and the share of it the resident lattice buffers may take;
# Mosaic refuses the kernel once they pass the cap.
MEGA_VMEM_LIMIT_BYTES = 100 * 2 ** 20
MEGA_LATTICE_BUDGET_BYTES = 96 * 2 ** 20


def mega_lattice_bytes(h: int, w: int, cell_dtype) -> int:
    """VMEM the megakernel keeps resident for an (h, w) lattice: the input
    and output blocks, plus an int32 working copy for narrow cells."""
    item = jnp.dtype(cell_dtype).itemsize
    return h * w * (2 * item + (0 if item == 4 else 4))


def check_mega_fits(h: int, w: int, cell_dtype) -> None:
    """Refuse a lattice the ``k_mcs`` megakernel cannot hold in VMEM."""
    need = mega_lattice_bytes(h, w, cell_dtype)
    if need > MEGA_LATTICE_BUDGET_BYTES:
        raise ValueError(
            f"k_mcs > 1 keeps the whole {h}x{w} {jnp.dtype(cell_dtype).name}"
            f" lattice resident in VMEM: {need} bytes exceeds the "
            f"megakernel's VMEM limit of {MEGA_LATTICE_BUDGET_BYTES} bytes "
            f"({MEGA_LATTICE_BUDGET_BYTES // 2 ** 20} MiB); use k_mcs=1 "
            "(one launch per MCS) or a sharded engine")


def _wrap(x, n: int):
    return jnp.where(x >= n, x - n, x)


def _mega_kernel(seeds_ref, shifts_ref, off_ref, dom_ref, dirs_ref,
                 grid_ref, out_ref, counts_ref, *scratch, t_eps: float,
                 t_eps_mu: float, k: int, iw: int, interior: int,
                 nbhd: int, gw: int, lgh: int, lgw: int, th: int, tw: int,
                 n_steps: int, n_counts: int, count_rows: int):
    """K Monte-Carlo steps over the whole (resident) lattice, one launch.

    The tile grid of the single-round kernel is folded into an in-kernel
    loop — TPU grid iterations run sequentially on a core, so nothing is
    lost; what is gained is that the K-step shift/sweep/count cycle never
    leaves VMEM. The torus shift of step t is not applied to the data:
    the kernel keeps the frame's origin (a, b) — the sum of the shifts so
    far — and addresses frame cell (x, y) at ((x + a) mod H, (y + b) mod
    W). The caller rolls the result by the final origin once, which is
    exactly the frame K jit-level ``jnp.roll`` rounds drift into. Counts
    are translation-invariant, so step t banks them from the buffer as
    it stands."""
    h = lgh * th
    w = lgw * tw
    work = scratch[0] if scratch else out_ref
    work[...] = grid_ref[...].astype(jnp.int32)

    def step(t, origin):
        a = _wrap(origin[0] + shifts_ref[t, 0], h)
        b = _wrap(origin[1] + shifts_ref[t, 1], w)

        def tile_body(tile_idx, _):
            ti = tile_idx // lgw
            tj = tile_idx % lgw
            tile_id = ((off_ref[0, 0] + ti).astype(jnp.uint32)
                       * jnp.uint32(gw)
                       + (off_ref[0, 1] + tj).astype(jnp.uint32))
            lanes, (cells, dirns, uact, udom) = _tile_proposals(
                tile_id, jnp.uint32(0), seeds_ref[t, 0], seeds_ref[t, 1],
                k=k, interior=interior, nbhd=nbhd)

            def prop_body(jj, _):
                cell = _pick(cells, lanes, jj)
                dirn = _pick(dirns, lanes, jj)
                x = ti * th + 1 + cell // iw
                y = tj * tw + 1 + cell % iw
                apply_proposal(
                    work, dom_ref, _wrap(x + a, h), _wrap(y + b, w),
                    _wrap(x + dirs_ref[dirn, 0] + a, h),
                    _wrap(y + dirs_ref[dirn, 1] + b, w),
                    _pick(uact, lanes, jj), _pick(udom, lanes, jj),
                    t_eps=t_eps, t_eps_mu=t_eps_mu)
                return 0

            lax.fori_loop(0, k, prop_body, 0)
            return 0

        lax.fori_loop(0, lgh * lgw, tile_body, 0)

        def count_body(c, acc):
            r = pl.multiple_of(c * count_rows, count_rows)
            rows = work[pl.ds(r, count_rows), :]
            return tuple(a_s + jnp.sum((rows == s).astype(jnp.int32))
                         for s, a_s in enumerate(acc))

        counts = lax.fori_loop(0, h // count_rows, count_body,
                               (jnp.int32(0),) * n_counts)
        for s in range(n_counts):       # static unroll over species + 1
            counts_ref[t, s] = counts[s]
        return a, b

    lax.fori_loop(0, n_steps, step, (jnp.int32(0), jnp.int32(0)))
    if scratch:
        out_ref[...] = work[...].astype(out_ref.dtype)


def escg_tile_rounds_fused(grid: jax.Array, seeds: jax.Array,
                           shifts: jax.Array, dom: jax.Array,
                           dirs: jax.Array, tile_shape: Tuple[int, int],
                           k_per_tile: int, t_eps: float, t_eps_mu: float,
                           species: int, neighbourhood: int = 4,
                           interpret: bool = False,
                           tile_offset: Optional[jax.Array] = None,
                           grid_tiles_w: Optional[int] = None):
    """K fused MCS per ``pallas_call`` (the ``k_mcs`` megakernel).

    seeds: (K, 2) uint32 per-MCS key words; shifts: (K, 2) int32 per-MCS
    torus shifts — both produced by ``engines.multi_round_inputs`` so the
    schedule is bit-identical to K driver-level calls of the one-round
    path. Returns ``(grid, counts)`` with counts (K, species + 1) int32,
    counts[t] == metrics.counts(grid after step t) — the per-MCS density
    stream the drivers need, banked in-kernel so no intermediate grid
    round-trips to HBM. The grid comes back in the drifted frame, exactly
    like the roll_back=False one-round path. ``tile_offset``/
    ``grid_tiles_w`` key counters by global tile identity when ``grid`` is
    one shard. Lattices past ``check_mega_fits`` are refused."""
    h, w = grid.shape
    th, tw = tile_shape
    lgh, lgw = h // th, w // tw
    n_steps = int(seeds.shape[0])
    check_mega_fits(h, w, grid.dtype)
    if grid_tiles_w is None:
        check_counter_capacity(lgh * lgw, k_per_tile)

    kern = functools.partial(
        _mega_kernel, t_eps=float(t_eps), t_eps_mu=float(t_eps_mu),
        k=int(k_per_tile), iw=tw - 2, interior=(th - 2) * (tw - 2),
        nbhd=int(neighbourhood),
        gw=int(lgw if grid_tiles_w is None else grid_tiles_w),
        lgh=lgh, lgw=lgw, th=th, tw=tw, n_steps=n_steps,
        n_counts=int(species) + 1, count_rows=math.gcd(h, 8))
    seeds = seeds.reshape(n_steps, 2).astype(jnp.uint32)
    shifts = shifts.reshape(n_steps, 2).astype(jnp.int32)
    if tile_offset is None:
        tile_offset = jnp.zeros((2,), jnp.int32)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)

    # single program, whole lattice resident: no grid, full-array blocks
    grid, counts = pl.pallas_call(
        kern,
        in_specs=[SMEM_FULL] * 5 + [vmem],
        out_specs=(vmem, SMEM_FULL),
        out_shape=(jax.ShapeDtypeStruct((h, w), grid.dtype),
                   jax.ShapeDtypeStruct((n_steps, int(species) + 1),
                                        jnp.int32)),
        scratch_shapes=([] if grid.dtype == jnp.int32
                        else [pltpu.VMEM((h, w), jnp.int32)]),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=MEGA_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="escg_rounds_fused",
    )(seeds, shifts, jnp.reshape(tile_offset, (1, 2)).astype(jnp.int32),
      dom, dirs, grid)
    origin = jnp.sum(shifts, axis=0)
    return jnp.roll(grid, (-(origin[0] % h), -(origin[1] % w)),
                    (0, 1)), counts
