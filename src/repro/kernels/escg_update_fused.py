"""Fused-PRNG sublattice kernels (§Perf H3 iter-2, beyond-paper).

The paper pre-generates random-number buffers in device memory and tunes
their size (--numRandoms, Fig 4.2). These kernels ELIMINATE that traffic
and the tuning knob: each tile derives its proposals from Philox-4x32
counters *inside* the kernel, in VMEM, at the moment of consumption — 16
bytes per elementary update of HBM traffic (4 random words) drop to zero;
what remains is the grid itself.

Counter layout (``kernels.philox.philox_proposal_fields``): c0 = global
tile_id * K + j (proposal index), c1 = round index, c2 = c3 = 0; key = two
words derived from the simulation PRNG key per MCS. Uniform ints via
modulus (the paper's own technique, §3.2.1): for a 32-bit word reduced
mod m the bias is at most m / 2^32, i.e. max(interior, nbhd) / 2^32 here
— e.g. < 2^-25 for the default 8x16 tile (interior 84), and < 2^-22 only
while interior < 2^10. ``check_counter_capacity`` guards the other edge:
c0 = tile_id * K + j must not wrap uint32, or distant tiles would
silently alias each other's streams.

**One round (``escg_tile_round_fused``): tiles on lanes.** Tiles are
disjoint and only their interiors are proposed, so every tile can play
its proposal j at the same time. The shifted (H, W) lattice is re-laid
(by XLA, around the kernel) as a tile-major int32 array of shape
(th * tw, T_pad): row p = r * tw + c is a cell position within a tile,
lane t is raster tile t, and T is padded to whole lane blocks of 128 or
256 lanes (``lane_layout``) with dummy tiles whose results are dropped.
The kernel's 1-D grid walks the lane blocks. In each, proposal j of
every tile of the block is one set of vector ops: Philox for eight j at
a time as full (8, lanes) vregs; cell and neighbour rows per lane
without division; one-hot reads (row iota == row, select, sum over
rows); the dominance lookup the same way, one-hot over the (S + 1)^2
entries of the table; the rules of ``repro.core.rules.pair_update``; one
masked select that writes both cells. Within a tile the proposals keep
their order, so the result is bit-identical to playing the tiles one by
one. Narrow (int8) lattices
are widened in the re-layout and narrowed on the way back.

**The ``k_mcs`` megakernel (``escg_tile_rounds_fused``)** keeps the
whole lattice resident in VMEM and plays each tile's proposals as a
scalar chain: TPU grid iterations run sequentially on a core, so folding
the tile grid into an in-kernel loop loses nothing there.

**Global tile identity.** ``tile_offset``/``grid_tiles_w`` let a shard of
a domain-decomposed lattice derive the SAME counters the single-device
kernel would: a tile's (i, j) position is offset by the shard's first
owned tile and raster-flattened against the GLOBAL tile-grid width. That
is the whole multi-device contract — the sharded engines'
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
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.rules import pair_update
from .escg_update import SMEM_FULL, apply_proposal
from .philox import philox_proposal_fields

LANES = 128          # lanes of one vreg column: 128 tiles
SUBLANES = 8         # proposals derived per Philox batch: one full vreg


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


# ------------------------- one round, tiles on lanes ---------------------- #

def lane_layout(n_tiles: int) -> Tuple[int, int]:
    """(tiles per lane block, tiles padded to whole blocks) of the
    one-round kernel. Two 128-lane columns per block let the scheduler
    overlap one column's serial chain (the reads' reductions, the
    dominance lookup, the rules) with the other's: on a v5e chip a
    3200^2 round with its re-layout takes 8.7 ms in place of 10.0, 7/8
    of the time. They are taken unless padding to 256 lanes, in place of
    128, makes more than 8/7 as many lanes, which would cost that back."""
    one, two = (-(-n_tiles // n) * n for n in (LANES, 2 * LANES))
    return (2 * LANES, two) if 7 * two <= 8 * one else (LANES, one)


def _round_kernel(seed_ref, round_ref, dirs_ref, table_ref, tile_ref,
                  cells_ref, out_ref, *, t_eps: float, t_eps_mu: float,
                  k: int, th: int, tw: int, nbhd: int, n_states: int):
    rows, lanes = out_ref.shape
    iw = tw - 2
    k0, k1 = seed_ref[0, 0], seed_ref[0, 1]
    round_idx = round_ref[0, 0]
    ctr0 = tile_ref[...] * jnp.uint32(k)          # (1, lanes): counter of j=0
    sub = lax.broadcasted_iota(jnp.uint32, (SUBLANES, lanes), 0)
    row = lax.broadcasted_iota(jnp.int32, (rows, lanes), 0)
    # neighbour offset of each direction, as a row step of the layout
    offs = [dirs_ref[d, 0] * tw + dirs_ref[d, 1] for d in range(nbhd)]
    # dominance table: row a * n_states + b holds D[a, b] and D[b, a]
    entry = lax.broadcasted_iota(jnp.int32, (table_ref.shape[0], lanes), 0)
    dom_sn = jnp.broadcast_to(table_ref[:, 0:1], entry.shape)
    dom_ns = jnp.broadcast_to(table_ref[:, 1:2], entry.shape)

    def positions(j0):
        """Rows of cell and neighbour, and the two draws, of proposals
        j0 .. j0 + 7 of every tile, as (8, lanes) arrays."""
        j = sub + j0.astype(jnp.uint32)
        cell, dirn, ua, ud = philox_proposal_fields(
            ctr0 + j, round_idx, k0, k1, (th - 2) * iw, nbhd)
        # interior row q = cell // iw by compares: cell lies at tile row
        # q + 1, column cell - q * iw + 1, i.e. layout row cell + 2q + tw + 1
        q = sum((cell >= i * iw).astype(jnp.int32) for i in range(1, th - 2))
        pos = cell + 2 * q + (tw + 1)
        off = jnp.zeros_like(pos) + offs[0]
        for d in range(1, nbhd):
            off = jnp.where(dirn == d, offs[d], off)
        npos = pos + off
        if k % SUBLANES:
            # proposals past K in the last batch point at no row: no-ops
            pos = jnp.where(j < k, pos, -1)
            npos = jnp.where(j < k, npos, -1)
        return pos, npos, ua, ud

    def play(x, pos, npos, ua, ud):
        """Proposal (pos, npos, ua, ud), (1, lanes) each, on every tile."""
        at_s = row == pos
        at_n = row == npos
        s = jnp.sum(jnp.where(at_s, x, 0), axis=0, keepdims=True)
        n = jnp.sum(jnp.where(at_n, x, 0), axis=0, keepdims=True)
        hit = entry == s * n_states + n
        p1 = jnp.sum(jnp.where(hit, dom_sn, 0.0), axis=0, keepdims=True)
        p2 = jnp.sum(jnp.where(hit, dom_ns, 0.0), axis=0, keepdims=True)
        new_s, new_n = pair_update(s, n, ua, ud, p1, p2, t_eps, t_eps_mu)
        return jnp.where(at_s, new_s, jnp.where(at_n, new_n, x))

    def batch(g, carry):
        pos, npos, ua, ud = positions(g * SUBLANES)
        for i in range(SUBLANES):                 # static unroll
            out_ref[...] = play(out_ref[...], pos[i:i + 1], npos[i:i + 1],
                                ua[i:i + 1], ud[i:i + 1])
        return carry

    out_ref[...] = cells_ref[...]
    lax.fori_loop(0, -(-k // SUBLANES), batch, 0)


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
    n_tiles = gh * gw
    if grid_tiles_w is None:
        # single-lattice call: the local tile grid IS the global one.
        # Sharded callers pass grid_tiles_w and guard with the true
        # global tile count themselves (core/sharded.py).
        check_counter_capacity(n_tiles, k_per_tile)
    lanes, t_pad = lane_layout(n_tiles)

    # tile-major layout: cells[r * tw + c, t] = grid[ti * th + r, tj * tw + c]
    cells = (grid.astype(jnp.int32).reshape(gh, th, gw, tw)
             .transpose(1, 3, 0, 2).reshape(th * tw, n_tiles))
    cells = jnp.pad(cells, ((0, 0), (0, t_pad - n_tiles)))
    # global raster tile id of each lane: the local (i, j) position offset
    # by this shard's first owned tile, flattened against the GLOBAL
    # tile-grid width (dummy lanes run past the grid; they are dropped)
    if tile_offset is None:
        tile_offset = jnp.zeros((2,), jnp.int32)
    off = jnp.asarray(tile_offset).astype(jnp.int32)
    ti, tj = np.divmod(np.arange(t_pad, dtype=np.int32), gw)
    tile_id = ((off[0] + ti).astype(jnp.uint32)
               * jnp.uint32(gw if grid_tiles_w is None else grid_tiles_w)
               + (off[1] + tj).astype(jnp.uint32)).reshape(1, t_pad)

    # the dominance table as a (pairs, 2) column pair for one-hot lookups:
    # row a * (S + 1) + b holds (D[a, b], D[b, a]); rows padded to vregs
    n_states = dom.shape[0]
    n_pairs = n_states * n_states
    table = jnp.stack([dom.reshape(-1), dom.T.reshape(-1)], axis=1)
    table = jnp.pad(table.astype(jnp.float32),
                    ((0, -n_pairs % SUBLANES), (0, 0)))

    kern = functools.partial(
        _round_kernel, t_eps=float(t_eps), t_eps_mu=float(t_eps_mu),
        k=int(k_per_tile), th=th, tw=tw, nbhd=int(neighbourhood),
        n_states=n_states)
    block = pl.BlockSpec((th * tw, lanes), lambda b: (0, b))
    # scalar operands stay 2-D: under vmap a batched 1-D operand would get
    # an illegal (1, n) block
    out = pl.pallas_call(
        kern,
        grid=(t_pad // lanes,),
        in_specs=[SMEM_FULL] * 3
        + [pl.BlockSpec(table.shape, lambda b: (0, 0)),
           pl.BlockSpec((1, lanes), lambda b: (0, b)), block],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((th * tw, t_pad), jnp.int32),
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="escg_round_fused",
    )(seed.reshape(1, 2).astype(jnp.uint32),
      jnp.reshape(round_idx, (1, 1)).astype(jnp.uint32),
      dirs, table, tile_id, cells)
    return (out[:, :n_tiles].reshape(th, tw, gh, gw).transpose(2, 0, 3, 1)
            .reshape(h, w).astype(grid.dtype))


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
