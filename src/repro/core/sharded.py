"""Multi-device ESCG: 2-D spatial domain decomposition with explicit halo
exchange (DESIGN.md §5; the ROADMAP "sharding" north-star).

The lattice shards as P('rows', 'cols') over a device grid (dr, dc). One
round, entirely inside a single ``shard_map`` region:

  1. **halo exchange**: the random sublattice shift (dy, dx) in
     [0,th) x [0,tw) is realized as a static-size halo — each device
     ``ppermute``s its first ``th`` rows (resp. ``tw`` cols) to the
     neighbouring device and dynamic-slices the shifted window out of the
     extended block. O(halo x perimeter) bytes per round, never a
     whole-lattice gather. (A global ``jnp.roll`` on the shard_map output
     miscompiled under jit on jax 0.4.37 — values got summed across the
     device axis; not reproduced on jax 0.9.0 — so the roll stays inside
     the shard_map region; see tests/test_sharded_engine.py.)
  2. **local update**: every device regenerates the per-tile Philox
     proposal streams for exactly the tiles it owns
     (``rng.tile_stream_batch`` keyed by global tile id) and runs the same
     per-tile sequential sweeps as the single-device engine. Proposals are
     restricted to tile interiors and device blocks are unions of tiles,
     so no device ever writes another device's cells — communication-free
     by construction, no atomics.
  3. the shift is accumulated, not rolled back (densities are
     translation-invariant; same policy as the sublattice engine).

Because the streams are keyed by global tile id, a sharded run is
**bit-identical to the single-device sublattice engine for ANY shard
layout** — (1,1), (2,2), (4,1), ... all produce the same trajectory. The
population counts the stasis early-exit consumes are computed on the
sharded lattice at the jit level; XLA lowers them to per-shard partial
bincounts + an all-reduce (the cross-device population reduction).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P
from jax import shard_map

from .engines import (BuiltEngine, _tiled_setup, fused_round_inputs,
                      multi_round_inputs)
from .lattice import DIRS
from .rng import ProposalBatch, round_shift, tile_stream_batch
from .sublattice import from_tiles, tile_update, to_tiles


# ------------------------- halo-exchange primitive ------------------------ #

def halo_roll(local: jax.Array, s: jax.Array, halo: int, axis_name: str,
              axis: int, n_shards: int, reverse: bool = False) -> jax.Array:
    """Distributed torus roll by a dynamic shift, via static-size halos.

    Rolls the GLOBAL lattice by ``-s`` (or ``+s`` when ``reverse``) along
    ``axis``, operating on the local block inside a shard_map region.
    Requires ``0 <= s < halo <= local block extent``: the wrapped sliver
    then crosses exactly one shard boundary, so a single ppermute of a
    static ``halo``-sized slab suffices; the dynamic part is a local
    dynamic_slice.
    """
    extent = local.shape[axis]
    if n_shards == 1:
        return jnp.roll(local, s if reverse else -s, axis)
    if not reverse:
        # new_local[i] = old[i][s:] ++ old[i+1][:s]
        head = lax.slice_in_dim(local, 0, halo, axis=axis)
        recv = lax.ppermute(head, axis_name,
                            [(i, (i - 1) % n_shards)
                             for i in range(n_shards)])
        ext = jnp.concatenate([local, recv], axis=axis)
        return lax.dynamic_slice_in_dim(ext, s, extent, axis=axis)
    # new_local[i] = old[i-1][B-s:] ++ old[i][:B-s]
    tail = lax.slice_in_dim(local, extent - halo, extent, axis=axis)
    recv = lax.ppermute(tail, axis_name,
                        [(i, (i + 1) % n_shards) for i in range(n_shards)])
    ext = jnp.concatenate([recv, local], axis=axis)
    return lax.dynamic_slice_in_dim(ext, halo - s, extent, axis=axis)


def shard_shift2d(local: jax.Array, shift: jax.Array,
                  tile_shape: Tuple[int, int], shard_grid: Tuple[int, int],
                  row_axis: str = "rows", col_axis: str = "cols",
                  reverse: bool = False) -> jax.Array:
    """Apply (or undo) the round's 2-D torus shift inside shard_map."""
    th, tw = tile_shape
    dr, dc = shard_grid
    local = halo_roll(local, shift[0], th, row_axis, 0, dr, reverse)
    local = halo_roll(local, shift[1], tw, col_axis, 1, dc, reverse)
    return local


# ------------------------------ local round ------------------------------- #

def _local_tile_ids(block_shape: Tuple[int, int],
                    tile_shape: Tuple[int, int], gw: int,
                    row_axis: str, col_axis: str) -> jax.Array:
    """Global tile ids (raster order) of the tiles this shard owns."""
    th, tw = tile_shape
    lgh, lgw = block_shape[0] // th, block_shape[1] // tw
    ri = lax.axis_index(row_axis)
    ci = lax.axis_index(col_axis)
    rows = ri * lgh + jnp.arange(lgh, dtype=jnp.int32)
    cols = ci * lgw + jnp.arange(lgw, dtype=jnp.int32)
    return (rows[:, None] * gw + cols[None, :]).reshape(-1)


def _update_tiles(local: jax.Array, props: ProposalBatch,
                  tile_shape: Tuple[int, int], t_eps: float, t_eps_mu: float,
                  dom: jax.Array, local_kernel: str = "jnp") -> jax.Array:
    """Per-tile sequential sweeps over one device's block.

    ``local_kernel`` selects the implementation (bit-identical paths, the
    single-device `pallas` vs `sublattice` guarantee lifted into the
    shard_map region): 'jnp' runs the vmapped ``tile_update`` scan, 'pallas'
    runs the VMEM-tiled ``kernels.escg_update`` kernel on the local block —
    one Pallas program per owned tile, proposals in local raster order.
    """
    if local_kernel == "pallas":
        from ..kernels import escg_update, ops as kernel_ops  # lazy: cycles
        return escg_update.escg_tile_round(
            local, props.cell, props.dirn, props.u_act, props.u_dom,
            dom, jnp.asarray(DIRS, jnp.int32), tile_shape, t_eps, t_eps_mu,
            interpret=kernel_ops._default_interpret())
    th, tw = tile_shape
    tiles = to_tiles(local, th, tw)
    upd = jax.vmap(lambda t, c, d, a, u: tile_update(
        t, ProposalBatch(c, d, a, u), t_eps, t_eps_mu, dom))
    tiles = upd(tiles, props.cell, props.dirn, props.u_act, props.u_dom)
    return from_tiles(tiles, local.shape[0], local.shape[1])


# ----------------------------- engine builder ----------------------------- #

def lattice_sharding(mesh: Mesh, row_axis: str = "rows",
                     col_axis: str = "cols") -> NamedSharding:
    return NamedSharding(mesh, P(row_axis, col_axis))


def round_stream_inputs(p, key: jax.Array, th: int, tw: int):
    """Per-MCS ``(stream, shift)`` pair consumed by ``make_local_round``,
    derived from one engine key EXACTLY like the single-device engine of
    the same local-kernel family (the bit-identity contract,
    ``EngineCaps.oracle_for``):

    * ``'jnp'`` / ``'pallas'``: ``stream`` is the proposal key of the
      ``split(key)`` pair, shift keyed by the other half — the
      ``_build_tiled`` schedule (oracle: ``sublattice``);
    * ``'fused'``: ``stream`` is the (2,) uint32 Philox seed words and the
      shift comes from ``fold_in(key, 1)`` — the ``pallas_fused``
      schedule (``engines.fused_round_inputs``).
    """
    if p.local_kernel == "fused":
        return fused_round_inputs(key, th, tw)
    kp, ks = jax.random.split(key)
    return kp, round_shift(ks, th, tw)


def make_local_round(p, dom, shard_grid: Tuple[int, int],
                     row_axis: str = "rows", col_axis: str = "cols"):
    """``local_round(gl, stream, shift)`` — one device-block's share of a
    round: halo shift, regenerate the owned tiles' streams, sweep.
    ``stream`` is the per-MCS proposal source from ``round_stream_inputs``
    (a PRNG key for the jnp/pallas sweeps, raw Philox seed words for the
    fused kernel).

    This is THE per-block computation both the ``sharded`` and the
    composed ``sharded_pod`` builders run inside their shard_map regions
    (sharded_pod vmaps it over its local trial slice); the cross-engine
    bit-identity contract depends on there being exactly one copy.

    ``local_kernel='fused'`` derives proposals IN-KERNEL from Philox
    counters keyed by global tile identity (the shard's tile offset +
    the global tile-grid width fold the counter): zero proposal arrays
    touch HBM inside the shard_map region, and the trajectory is
    bit-identical to the single-device ``pallas_fused`` engine for every
    mesh factorization (DESIGN.md §6).
    """
    t_eps, t_eps_mu = p.action_thresholds()
    th, tw, _, k_per, interior = _tiled_setup(p)
    gw = p.length // tw
    dr, dc = shard_grid
    # NOTE: jnp constants (dom, DIRS) are created inside the returned
    # closures, not here — this factory may run lazily under an outer jit
    # trace (the k_mcs shard_map cache), and a constant captured from one
    # trace leaks into the next (UnexpectedTracerError).

    if p.local_kernel == "fused":
        from ..kernels import escg_update_fused, ops as kernel_ops  # lazy
        interp = kernel_ops._default_interpret()

        def local_round(gl, seed, shift):
            gl = shard_shift2d(gl, shift, (th, tw), (dr, dc), row_axis,
                               col_axis)
            lgh, lgw = gl.shape[0] // th, gl.shape[1] // tw
            off = jnp.stack([lax.axis_index(row_axis) * lgh,
                             lax.axis_index(col_axis) * lgw])
            return escg_update_fused.escg_tile_round_fused(
                gl, seed, jnp.uint32(0), jnp.asarray(dom, jnp.float32),
                jnp.asarray(DIRS, jnp.int32), (th, tw), k_per,
                t_eps, t_eps_mu, p.neighbourhood, interpret=interp,
                tile_offset=off, grid_tiles_w=gw)
        return local_round

    def local_round(gl, kp, shift):
        gl = shard_shift2d(gl, shift, (th, tw), (dr, dc), row_axis, col_axis)
        tids = _local_tile_ids(gl.shape, (th, tw), gw, row_axis, col_axis)
        props = tile_stream_batch(kp, tids, k_per, interior, p.neighbourhood)
        return _update_tiles(gl, props, (th, tw), t_eps, t_eps_mu,
                             jnp.asarray(dom, jnp.float32),
                             local_kernel=p.local_kernel)
    return local_round


def make_local_multi_round(p, dom, shard_grid: Tuple[int, int],
                           k_steps: int, row_axis: str = "rows",
                           col_axis: str = "cols"):
    """``local_multi(gl, seeds (K, 2), shifts (K, 2)) -> (gl, counts)``
    — K fused MCS of one device-block inside the shard_map region, with
    GLOBAL per-step species counts (K, species + 1) banked alongside (the
    per-MCS density stream the drivers need for stasis detection).

    Two shapes, one contract (bit-identical to K ``local_round`` calls):

    * ``shard_grid == (1, 1)`` (every pod slice of sharded_pod, and
      sharded on one device): the whole lattice is block-resident, so the
      TRUE megakernel runs — K shift/sweep/count cycles in ONE
      ``pallas_call``, in-kernel torus roll, zero HBM round-trips between
      steps. Counts come out of the kernel already global.
    * multi-shard: the halo exchange is a cross-device collective that
      cannot live inside a ``pallas_call``, so K single-round kernels run
      back-to-back inside ONE shard_map region (launch overhead still
      amortized K× at the jit level); per-shard partial counts are
      ``psum``med into global ones.
    """
    t_eps, t_eps_mu = p.action_thresholds()
    th, tw, _, k_per, _ = _tiled_setup(p)
    gw = p.length // tw
    dr, dc = shard_grid
    from ..kernels import escg_update_fused, ops as kernel_ops  # lazy
    escg_update_fused.check_counter_capacity(
        (p.height // th) * (p.length // tw), k_per)
    interp = kernel_ops._default_interpret()
    n_counts = p.species + 1
    # trace safety: this factory runs lazily under the drivers' jitted
    # chunks (the per-k_steps shard_map cache), so jnp constants must be
    # created inside local_multi — see make_local_round

    if dr == dc == 1:
        def local_multi(gl, seeds, shifts):
            return escg_update_fused.escg_tile_rounds_fused(
                gl, seeds, shifts, jnp.asarray(dom, jnp.float32),
                jnp.asarray(DIRS, jnp.int32), (th, tw), k_per, t_eps,
                t_eps_mu, p.species, p.neighbourhood, interpret=interp,
                grid_tiles_w=gw)
        return local_multi

    single = make_local_round(p, dom, shard_grid, row_axis, col_axis)

    def local_multi(gl, seeds, shifts):
        counts = []
        for t in range(k_steps):        # static: K kernels, one region
            gl = single(gl, seeds[t], shifts[t])
            gi = gl.astype(jnp.int32)
            counts.append(jnp.stack([jnp.sum((gi == s).astype(jnp.int32))
                                     for s in range(n_counts)]))
        cnts = lax.psum(jnp.stack(counts), (row_axis, col_axis))
        return gl, cnts
    return local_multi


def build_engine(params, dom: jax.Array,
                 mesh: Optional[Mesh] = None,
                 row_axis: str = "rows",
                 col_axis: str = "cols") -> BuiltEngine:
    """Registry builder for engine='sharded'.

    ``mesh`` defaults to a lattice mesh over all local devices, shaped by
    ``params.shard_grid`` (auto-factored when None; see
    parallel.sharding.lattice_mesh).
    """
    from ..parallel.sharding import lattice_mesh  # lazy: parallel -> models

    p = params.validate()
    # same bookkeeping as the single-device tiled engines — the bit-identity
    # guarantee depends on k_per/interior matching exactly
    th, tw, n_tiles, k_per, _ = _tiled_setup(p)

    if mesh is None:
        mesh = lattice_mesh(p.shard_grid, p.height, p.length, th, tw,
                            row_axis=row_axis, col_axis=col_axis)
    dr, dc = mesh.shape[row_axis], mesh.shape[col_axis]
    if (p.height // dr) % th or (p.length // dc) % tw:
        raise ValueError(
            f"device blocks ({p.height // dr}x{p.length // dc}) must be "
            f"unions of {th}x{tw} tiles")

    grid_spec = P(row_axis, col_axis)
    local_round = make_local_round(p, dom, (dr, dc), row_axis, col_axis)

    round_fn = shard_map(local_round, mesh=mesh,
                         in_specs=(grid_spec, P(), P()),
                         out_specs=grid_spec, check_vma=False)

    def one_mcs(grid, key):
        stream, shift = round_stream_inputs(p, key, th, tw)
        grid = round_fn(grid, stream, shift)
        attempts = jnp.int32(n_tiles * k_per)
        return grid, attempts, attempts

    multi_mcs = None
    if p.local_kernel == "fused":
        # k_mcs megakernel path: one shard_map region per K-step group,
        # cached per distinct K (the driver only uses K and the remainder)
        multi_fns = {}

        def _multi_fn(k_steps: int):
            if k_steps not in multi_fns:
                local_multi = make_local_multi_round(
                    p, dom, (dr, dc), k_steps, row_axis, col_axis)
                multi_fns[k_steps] = shard_map(
                    local_multi, mesh=mesh,
                    in_specs=(grid_spec, P(), P()),
                    out_specs=(grid_spec, P()), check_vma=False)
            return multi_fns[k_steps]

        def multi_mcs(grid, key, k_steps):
            key, seeds, shifts = multi_round_inputs(key, th, tw, k_steps)
            grid, counts = _multi_fn(k_steps)(grid, seeds, shifts)
            attempts = jnp.int32(k_steps * n_tiles * k_per)
            return grid, key, counts, attempts, attempts

    return BuiltEngine(one_mcs, grid_sharding=lattice_sharding(
        mesh, row_axis, col_axis), multi_mcs=multi_mcs)


# --------------------- explicit-proposal round (tests) -------------------- #

def sharded_run_round(grid: jax.Array, props: ProposalBatch,
                      shift: jax.Array, tile_shape: Tuple[int, int],
                      t_eps: float, t_eps_mu: float, dom: jax.Array,
                      mesh: Mesh, row_axis: str = "data",
                      col_axis: str = "model",
                      roll_back: bool = True,
                      local_kernel: str = "jnp") -> jax.Array:
    """One shifted-window round with externally supplied proposals in
    global raster tile order, shape (T, K). Bit-identical to
    ``sublattice.run_round`` on the same inputs; jit-safe (all rolls happen
    inside the shard_map region)."""
    h, w = grid.shape
    th, tw = tile_shape
    gh, gw = h // th, w // tw
    dr = mesh.shape[row_axis]
    dc = mesh.shape[col_axis]
    if (h // dr) % th or (w // dc) % tw:
        raise ValueError("device blocks must be unions of tiles")

    grid_spec = P(row_axis, col_axis)
    prop_spec = P(row_axis, col_axis, None)

    def reshape_props(a):
        return a.reshape(gh, gw, -1)

    def local_round(gl, sh, cell, dirn, ua, ud):
        gl = shard_shift2d(gl, sh, (th, tw), (dr, dc), row_axis, col_axis)
        k = cell.shape[-1]
        props_l = ProposalBatch(cell.reshape(-1, k), dirn.reshape(-1, k),
                                ua.reshape(-1, k), ud.reshape(-1, k))
        gl = _update_tiles(gl, props_l, (th, tw), t_eps, t_eps_mu, dom,
                           local_kernel=local_kernel)
        if roll_back:
            gl = shard_shift2d(gl, sh, (th, tw), (dr, dc), row_axis,
                               col_axis, reverse=True)
        return gl

    update = shard_map(
        local_round, mesh=mesh,
        in_specs=(grid_spec, P(), prop_spec, prop_spec, prop_spec,
                  prop_spec),
        out_specs=grid_spec, check_vma=False)

    return update(grid, shift, reshape_props(props.cell),
                  reshape_props(props.dirn), reshape_props(props.u_act),
                  reshape_props(props.u_dom))


def make_sharded_simulation(params, dom, mesh: Mesh,
                            row_axis: str = "data",
                            col_axis: str = "model",
                            roll_back: bool = True):
    """Returns (grid_sharding, jitted one_mcs(grid, key) -> grid) on an
    explicit mesh — the notebook/driver-facing wrapper.

    Unlike the registered engine (which accumulates the random window
    shift; densities are translation-invariant), this wrapper rolls the
    lattice back every MCS by default, so snapshots and spatial analyses
    of the returned grid stay in the fixed reference frame. Pass
    ``roll_back=False`` for the cheaper drifting-frame variant.
    """
    p = params.validate()
    if p.engine not in ("sublattice", "pallas", "sharded"):
        raise ValueError("sharded ESCG uses a tiled engine")
    t_eps, t_eps_mu = p.action_thresholds()
    th, tw, n_tiles, k_per, interior = _tiled_setup(p)
    dom_j = jnp.asarray(dom, jnp.float32)
    tile_ids = jnp.arange(n_tiles, dtype=jnp.int32)

    @jax.jit
    def one_mcs(grid, key):
        kp, ks = jax.random.split(key)
        props = tile_stream_batch(kp, tile_ids, k_per, interior,
                                  p.neighbourhood)
        shift = round_shift(ks, th, tw)
        return sharded_run_round(grid, props, shift, (th, tw), t_eps,
                                 t_eps_mu, dom_j, mesh, row_axis, col_axis,
                                 roll_back=roll_back)

    return lattice_sharding(mesh, row_axis, col_axis), one_mcs
