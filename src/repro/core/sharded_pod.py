"""Composed pod x grid mesh engine: IID trials x domain decomposition
(DESIGN.md §6; the ROADMAP "compose the two axes" north-star item).

PR 1 scaled the grid axis (one big lattice over ('rows','cols'), halo
exchange) and PR 2 scaled the trial axis (many IID lattices over a 1-D
'pod' mesh); this module composes them on a single
``('pod', 'rows', 'cols')`` device mesh — the regime the paper's
replication studies actually need (many IID trials x large grids; sPEGG
and BioDynaMo both run the replicate axis and the spatial domain on the
accelerator simultaneously).

Layout: a batch of trial lattices, shape (n_trials, H, W), shards as
``P('pod', 'rows', 'cols')`` — pod group ``g`` owns ``n_trials / P``
whole replicates, and within the group each replicate is domain-decomposed
exactly like the ``sharded`` engine. One MCS runs inside one ``shard_map``
region over all three axes: the per-trial local round (halo exchange +
per-tile Philox sweeps, ``core.sharded``) is ``jax.vmap``-ed over the
local trial slice. ppermute/axis_index batch cleanly under vmap, and the
pod axis needs no collectives at all (IID trials never interact).

**Bit-identity for every factorization.** Both axes key by stable global
identity (DESIGN.md §3): trial ``t`` is keyed by ``fold_in(base, t)`` and
tile ``i`` of trial ``t`` by ``fold_in(round key, global tile id)`` —
never by pod width, shard layout, or padding. A ``(P, R, C)`` run is
therefore bit-identical to the ``(1, 1, 1)`` layout, which in turn is
bit-identical to the single-device ``sublattice`` engine
(tests/test_properties.py asserts this for every factorization of 8 fake
devices).

The in-region tile sweeps honour ``params.local_kernel``: 'jnp' and
'pallas' run the same VMEM-tiled paths as the single-device engines
(oracle: ``sublattice``), and 'fused' derives proposals in-kernel from
Philox counters keyed by global (tile, trial) identity — zero proposal
arrays in HBM, bit-identical to the single-device ``pallas_fused`` engine
for every mesh factorization (oracle family two; DESIGN.md §6).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P
from jax import shard_map

from .engines import BuiltEngine, _tiled_setup, multi_round_inputs
from .sharded import (build_engine as build_grid_engine, make_local_round,
                      make_local_multi_round, round_stream_inputs)

POD_AXIS, ROW_AXIS, COL_AXIS = "pod", "rows", "cols"


def build_engine(params, dom: jax.Array,
                 mesh: Optional[Mesh] = None) -> BuiltEngine:
    """Registry builder for engine='sharded_pod'.

    ``mesh`` defaults to ``parallel.sharding.pod_lattice_mesh`` shaped by
    ``params.mesh_shape`` (all local devices on the pod axis when None).
    Returns a BuiltEngine carrying BOTH contracts: ``one_mcs`` advances a
    single lattice on the ('rows','cols') sub-mesh of pod group 0 (so
    ``simulate`` works unchanged), and ``one_mcs_batch`` advances a whole
    trial batch on the full composed mesh (consumed by
    ``trials.run_trials``; see DESIGN.md §6).
    """
    from ..parallel.sharding import pod_lattice_mesh  # lazy: parallel->models

    p = params.validate()
    th, tw, n_tiles, k_per, _ = _tiled_setup(p)

    if mesh is None:
        mesh = pod_lattice_mesh(p.mesh_shape, p.height, p.length, th, tw)
    pw = mesh.shape[POD_AXIS]
    dr, dc = mesh.shape[ROW_AXIS], mesh.shape[COL_AXIS]
    if (p.height // dr) % th or (p.length // dc) % tw:
        raise ValueError(
            f"device blocks ({p.height // dr}x{p.length // dc}) must be "
            f"unions of {th}x{tw} tiles")

    # single-lattice path (simulate): the grid axes of pod group 0
    sub = build_grid_engine(p, dom, mesh=Mesh(mesh.devices[0],
                                              (ROW_AXIS, COL_AXIS)))

    batch_spec = P(POD_AXIS, ROW_AXIS, COL_AXIS)
    pod_spec = P(POD_AXIS)

    # THE per-block round the sharded engine runs (one shared definition,
    # core.sharded.make_local_round), vmapped over the local trial slice
    local_round = make_local_round(p, dom, (dr, dc), ROW_AXIS, COL_AXIS)

    round_fn = shard_map(
        lambda gs, kps, shifts: jax.vmap(local_round)(gs, kps, shifts),
        mesh=mesh, in_specs=(batch_spec, pod_spec, pod_spec),
        out_specs=batch_spec, check_vma=False)

    def one_mcs_batch(grids, keys):
        """Advance every trial one MCS. ``grids``: (n, H, W) on
        ``batch_sharding``; ``keys``: (n, 2) per-trial keys on
        ``key_sharding``. Per-trial key usage matches the single-lattice
        engine of the same local-kernel family exactly
        (``sharded.round_stream_inputs``: split -> proposal/shift keys for
        jnp/pallas, the pallas_fused Philox-seed schedule for 'fused'), so
        trial t's trajectory is bit-identical to running it alone."""
        streams, shifts = jax.vmap(
            lambda k: round_stream_inputs(p, k, th, tw))(keys)
        grids = round_fn(grids, streams, shifts)
        att = jnp.full((grids.shape[0],), n_tiles * k_per, jnp.int32)
        return grids, att, att

    multi_mcs_batch = None
    if p.local_kernel == "fused":
        # k_mcs megakernel over the composed mesh: the per-block K-step
        # local multi-round (core.sharded.make_local_multi_round — the
        # TRUE megakernel when (dr, dc) == (1, 1)) vmapped over each pod
        # group's trial slice; per-step counts come back per trial
        multi_fns = {}

        def _multi_fn(k_steps: int):
            if k_steps not in multi_fns:
                local_multi = make_local_multi_round(
                    p, dom, (dr, dc), k_steps, ROW_AXIS, COL_AXIS)
                multi_fns[k_steps] = shard_map(
                    lambda gs, seeds, shifts:
                        jax.vmap(local_multi)(gs, seeds, shifts),
                    mesh=mesh, in_specs=(batch_spec, pod_spec, pod_spec),
                    out_specs=(batch_spec, pod_spec), check_vma=False)
            return multi_fns[k_steps]

        def multi_mcs_batch(grids, keys, k_steps):
            """K MCS for every trial in one region: per-trial K-step fused
            schedules (bit-identical key chain to K one_mcs_batch calls),
            counts (n, K, species + 1)."""
            keys, seeds, shifts = jax.vmap(
                lambda k: multi_round_inputs(k, th, tw, k_steps))(keys)
            grids, counts = _multi_fn(k_steps)(grids, seeds, shifts)
            att = jnp.full((grids.shape[0],), k_steps * n_tiles * k_per,
                           jnp.int32)
            return grids, keys, counts, att, att

    return BuiltEngine(
        one_mcs=sub.one_mcs,
        grid_sharding=sub.grid_sharding,
        one_mcs_batch=one_mcs_batch,
        batch_sharding=NamedSharding(mesh, batch_spec),
        key_sharding=NamedSharding(mesh, pod_spec),
        pod_width=pw,
        multi_mcs=sub.multi_mcs,
        multi_mcs_batch=multi_mcs_batch,
    )
