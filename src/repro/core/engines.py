"""First-class engine registry (DESIGN.md §2, grown multi-device).

Every update engine registers a builder plus capability metadata here;
``simulation.simulate`` / ``run_trials`` and the CLI resolve engines through
this table instead of an if/elif ladder. Adding an engine is one
``@register(...)`` decorator — params validation, CLI choices and the
README engine matrix all follow automatically.

Engine contract: ``build(params, dom) -> BuiltEngine`` where
``one_mcs(grid, key) -> (grid, kept, attempts)`` advances one Monte-Carlo
step (N elementary updates) fully on-device. Engines whose caps set
``physics_operand`` also take ``one_mcs(grid, key, point)``: the physics
point (:class:`Physics`: the dominance matrix and the two action
thresholds) as device operands, so that one compiled program runs a
batch of trials each with its own point (a ``run_trials`` sweep,
DESIGN.md §4). Without ``point`` they run the point they were built
with, as every single-point call does. ``grid_sharding`` is non-None
for multi-device engines: the driver ``device_put``s the lattice onto it
before the first chunk and every array op thereafter stays
device-resident.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import partial
from typing import Callable, Dict, NamedTuple, Optional, Tuple, TYPE_CHECKING

import jax
import jax.numpy as jnp

from . import batched as batched_mod
from . import reference as reference_mod
from . import sublattice as sublattice_mod
from .rng import proposal_batch, round_shift, tile_stream_batch

if TYPE_CHECKING:  # avoid a runtime cycle: params validates via this module
    from .params import EscgParams


class Physics(NamedTuple):
    """One physics point as device operands: the padded (S+1, S+1)
    float32 dominance matrix and the float32 action thresholds
    (``EscgParams.action_thresholds``). A batch of points stacks each
    field on a leading axis."""
    dom: jax.Array
    t_eps: jax.Array
    t_eps_mu: jax.Array


def physics(p: "EscgParams", dom) -> Physics:
    """The physics point of ``p`` with dominance matrix ``dom``."""
    t_eps, t_eps_mu = p.action_thresholds()
    return Physics(jnp.asarray(dom, jnp.float32), jnp.float32(t_eps),
                   jnp.float32(t_eps_mu))


def stepper(one_mcs: Callable, point: Optional[Physics]) -> Callable:
    """``one_mcs(grid, key)`` with ``point`` bound, where there is one
    (None: the engine's own point, the only form of engines without
    ``EngineCaps.physics_operand``)."""
    if point is None:
        return one_mcs
    return lambda grid, key: one_mcs(grid, key, point)


class BuiltEngine(NamedTuple):
    """A ready-to-run engine instance for one (params, dominance) pair.

    ``one_mcs`` advances ONE lattice. Engines whose caps declare a ``pod``
    mesh axis (DESIGN.md §6) additionally provide ``one_mcs_batch``, which
    advances a whole batch of IID trial lattices laid out on a composed
    ``('pod', 'rows', 'cols')`` mesh: ``batch_sharding``/``key_sharding``
    are where the trial driver must place the stacked grids and per-trial
    keys, and ``pod_width`` is the trial-axis device count the batch must
    pad to.
    """
    one_mcs: Callable[[jax.Array, jax.Array],
                      Tuple[jax.Array, jax.Array, jax.Array]]
    grid_sharding: Optional[jax.sharding.Sharding] = None
    one_mcs_batch: Optional[Callable[[jax.Array, jax.Array],
                                     Tuple[jax.Array, jax.Array,
                                           jax.Array]]] = None
    batch_sharding: Optional[jax.sharding.Sharding] = None
    key_sharding: Optional[jax.sharding.Sharding] = None
    pod_width: int = 1
    # k_mcs megakernel entry points (DESIGN.md §6). ``multi_mcs(grid, key,
    # k_steps)`` advances K Monte-Carlo steps in one launch and returns
    # (grid, key', counts, kept, attempts) with counts (K, species+1) —
    # the per-MCS density stream the drivers would otherwise compute one
    # metrics.counts at a time. k_steps is static at trace time. The key
    # is split INSIDE exactly like K driver-level one_mcs calls would, so
    # trajectories stay bit-identical to k_mcs=1. ``multi_mcs_batch`` is
    # the composed-mesh analog over a trial batch: (grids, keys, k_steps)
    # -> (grids, keys', counts (n, K, species+1), kept (n,), att (n,)).
    multi_mcs: Optional[Callable] = None
    multi_mcs_batch: Optional[Callable] = None
    # observable hook (DESIGN.md §11): ``observe(grid, counts) ->
    # (obs_width,) float32`` — one streamed ring-buffer row, evaluated
    # inside the drivers' jitted chunks at per-MCS cadence. Non-None
    # exactly when ``params.observables`` is non-empty; ``engines.build``
    # attaches the registry-generic implementation
    # (observables.build_observe) for every engine family, so the
    # supported set is identical across sublattice/sharded/sharded_pod x
    # local kernels by construction. Must never consume PRNG state —
    # observables-on/off bit-identity is part of the engine contract.
    observe: Optional[Callable[[jax.Array, jax.Array], jax.Array]] = None


@dataclass(frozen=True)
class EngineCaps:
    """Static capability metadata, consumed by params validation, the
    trial runner and the docs engine matrix (DESIGN.md §2)."""
    flux_only: bool = False    # requires periodic (torus) boundaries
    tiled: bool = False        # consumes params.tile; tile must divide grid
    multi_device: bool = False  # domain-decomposed across jax.devices()
    vmappable: bool = True     # usable under vmap (trials.run_trials)
    trial_shardable: bool = True  # safe to shard the vmapped trial axis
                               # across devices (DESIGN.md §4); requires
                               # vmappable and no internal collectives
    mesh_axes: Tuple[str, ...] = ()  # device-mesh axes the engine owns;
                               # ('rows','cols') = grid decomposition (§5),
                               # ('pod','rows','cols') = composed trial x
                               # grid mesh (§6). Consumed by params
                               # validation of params.mesh_shape and by the
                               # trial runner's composition check.
    local_kernels: Tuple[str, ...] = ()  # values of params.local_kernel the
                               # engine accepts ('jnp', 'pallas', 'fused');
                               # empty = the knob is ignored
    multi_mcs: bool = False    # supports params.k_mcs > 1 (the grid-
                               # resident multi-MCS megakernel, DESIGN.md
                               # §6); only meaningful for the fused-Philox
                               # family — its in-kernel counter schedule
                               # is what makes K steps per launch possible
    equiv_oracle: Optional[str] = None  # engine this one is bit-identical
                               # to at the one_mcs level (same key -> same
                               # trajectory); drives the registry-wide
                               # cross-engine equivalence suite
    observables: Optional[Tuple[str, ...]] = None
                               # streaming observables (DESIGN.md §11)
                               # the engine supports; None = the full
                               # registry (core/observables.py) — every
                               # registered observable is a pure jit-level
                               # grid/counts read, so engines only
                               # restrict this when their step hides the
                               # lattice from XLA. Params validation
                               # checks requested names against it.
    equiv_oracles: Tuple[Tuple[str, str], ...] = ()
                               # per-local-kernel oracle overrides as
                               # (local_kernel, oracle) pairs: a local
                               # kernel with its own PRNG scheme belongs to
                               # a different bit-identity family (e.g.
                               # 'fused' -> 'pallas_fused'); resolve via
                               # oracle_for()
    physics_operand: bool = False  # one_mcs also takes the physics
                               # point (Physics) as an operand, so a trial
                               # batch may mix points (trials.run_trials
                               # over several scenarios). Engines without
                               # it compile their rates in.
    description: str = ""
    paper: str = ""            # paper algorithm / figure it reproduces

    def oracle_for(self, local_kernel: str = "jnp") -> Optional[str]:
        """The bit-identity oracle engine for this engine running with
        ``local_kernel`` — ``equiv_oracles`` overrides first, then the
        kernel-independent ``equiv_oracle`` (DESIGN.md §2). The
        equivalence suite (tests/test_engine_equivalence.py) enforces one
        contract per (engine, local kernel) pair through this."""
        for lk, oracle in self.equiv_oracles:
            if lk == local_kernel:
                return oracle
        return self.equiv_oracle

    @property
    def pod_composable(self) -> bool:
        """True when the trial axis rides a ``pod`` mesh axis: the trial
        driver may run IID batches of this engine on a composed
        ``('pod', 'rows', 'cols')`` mesh (DESIGN.md §6)."""
        return "pod" in self.mesh_axes

    @property
    def trial_axis(self) -> str:
        """Human-readable trial-axis support (engine matrix column)."""
        if self.pod_composable:
            return "pod×grid composed mesh"
        if self.vmappable and self.trial_shardable:
            return "pod-sharded vmap"
        if self.vmappable:
            return "vmap (1 device)"
        return "—"


@dataclass(frozen=True)
class EngineSpec:
    name: str
    caps: EngineCaps
    build: Callable[["EscgParams", jax.Array], BuiltEngine] = field(
        repr=False, default=None)


_REGISTRY: Dict[str, EngineSpec] = {}


def register(name: str, caps: EngineCaps):
    """Decorator: register ``build(params, dom) -> BuiltEngine`` under
    ``name``. Re-registration replaces (supports hot reload in notebooks)."""
    def deco(build_fn):
        _REGISTRY[name] = EngineSpec(name=name, caps=caps, build=build_fn)
        return build_fn
    return deco


def engine_names() -> Tuple[str, ...]:
    return tuple(_REGISTRY)


def engine_specs() -> Tuple[EngineSpec, ...]:
    return tuple(_REGISTRY.values())


def get_engine(name: str) -> EngineSpec:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown engine {name!r}; registered: {engine_names()}"
        ) from None


def validate_params(p: "EscgParams") -> None:
    """Capability-driven validation (called from EscgParams.validate).

    Mesh-layout legality lives HERE, with the registry, not with the
    drivers: an engine's ``mesh_axes`` decide whether ``params.mesh_shape``
    is meaningful and what rank it must have (DESIGN.md §6)."""
    spec = get_engine(p.engine)
    if spec.caps.flux_only and not p.flux:
        raise ValueError(
            f"engine {p.engine!r} requires flux (periodic) boundaries; "
            "use reference/batched for reflecting boundaries")
    if spec.caps.tiled:
        th, tw = p.tile
        if th < 3 or tw < 3:
            raise ValueError("tile dims must be >= 3 (need interior)")
        if p.height % th or p.length % tw:
            raise ValueError(f"tile {p.tile} must divide lattice "
                             f"{p.height}x{p.length}")
    if spec.caps.multi_device and p.shard_grid is not None:
        dr, dc = p.shard_grid
        if dr < 1 or dc < 1:
            raise ValueError("shard_grid dims must be >= 1")
    if p.local_kernel not in ("jnp", "pallas", "fused"):
        raise ValueError("local_kernel must be 'jnp', 'pallas' or 'fused'")
    # engines that declare supported kernels accept exactly those; engines
    # with no declaration ignore the knob (same rule as params.tile)
    if spec.caps.local_kernels and \
            p.local_kernel not in spec.caps.local_kernels:
        raise ValueError(
            f"engine {p.engine!r} supports local_kernel in "
            f"{spec.caps.local_kernels}, got {p.local_kernel!r}")
    if p.k_mcs < 1:
        raise ValueError(f"k_mcs must be >= 1, got {p.k_mcs}")
    if p.k_mcs > 1:
        if not spec.caps.multi_mcs:
            raise ValueError(
                f"engine {p.engine!r} does not support k_mcs > 1 (the "
                "multi-MCS megakernel belongs to the fused-Philox family: "
                "pallas_fused, or sharded/sharded_pod with "
                "local_kernel='fused')")
        if spec.caps.local_kernels and p.local_kernel != "fused":
            raise ValueError(
                f"k_mcs > 1 requires local_kernel='fused' on engine "
                f"{p.engine!r} (got {p.local_kernel!r}): only the "
                "in-kernel Philox schedule can thread K MCS through one "
                "launch")
        # the megakernel holds each device's whole block in VMEM; where the
        # block is the whole lattice, refuse here what Mosaic would refuse
        # (kernels/escg_update_fused.py checks every block at trace time)
        whole = (p.engine == "pallas_fused"
                 or (p.engine == "sharded" and p.shard_grid == (1, 1))
                 or (p.engine == "sharded_pod"
                     and (p.mesh_shape is None
                          or tuple(p.mesh_shape[1:]) == (1, 1))))
        if whole:
            from ..kernels.escg_update_fused import check_mega_fits  # lazy
            check_mega_fits(p.height, p.length, p.cell_dtype)
    if p.obs_capacity < 0:
        raise ValueError(f"obs_capacity must be >= 0, got {p.obs_capacity}")
    if p.observables:
        from . import observables as obs_mod  # lazy: avoid import cycle
        for name in p.observables:
            obs_mod.get_observable(name)     # raises on unknown names
            if spec.caps.observables is not None \
                    and name not in spec.caps.observables:
                raise ValueError(
                    f"engine {p.engine!r} supports observables "
                    f"{spec.caps.observables}, got {name!r} "
                    "(EngineCaps.observables rails, DESIGN.md §11)")
    if p.mesh_shape is not None:
        if not spec.caps.pod_composable:
            raise ValueError(
                f"engine {p.engine!r} does not lay devices on a "
                f"('pod','rows','cols') mesh (mesh_axes="
                f"{spec.caps.mesh_axes}); mesh_shape only applies to "
                "pod-composable engines like 'sharded_pod'")
        if len(p.mesh_shape) != len(spec.caps.mesh_axes):
            raise ValueError(
                f"mesh_shape {p.mesh_shape} must have one entry per mesh "
                f"axis {spec.caps.mesh_axes}")
        if any(d < 1 for d in p.mesh_shape):
            raise ValueError("mesh_shape dims must be >= 1")


def build(params: "EscgParams", dom: Optional[jax.Array] = None
          ) -> BuiltEngine:
    """Resolve ``params.engine`` and build its one-MCS function.

    Also accepts a scenario-layer ``Scenario`` (DESIGN.md §10) in place of
    the flat params: it is composed with default engine/run configs, and
    ``dom=None`` then resolves the dominance network through the scenario
    registry."""
    from .scenarios import resolve_config  # lazy: scenarios imports us
    params, dom = resolve_config(params, dom)
    if dom is None:
        # same default as simulate(): the circulant C(S,{1}) cycle
        from . import dominance as dom_mod
        dom = dom_mod.circulant(params.species)
    if not isinstance(dom, jax.Array):
        dom = jnp.asarray(dom, jnp.float32)
    built = get_engine(params.engine).build(params, dom)
    if params.observables and built.observe is None:
        # registry-generic observe hook (DESIGN.md §11): one jit-level
        # implementation serves every engine family — on sharded grids
        # the reductions lower to per-shard partials + all-reduce, the
        # same path as the stasis counts. Builders may pre-attach a
        # specialized hook; absent that, every engine gets the same set.
        from . import observables as obs_mod  # lazy: avoid import cycle
        hook = obs_mod.build_observe(params)
        if built.grid_sharding is not None:
            # pin the row replicated across the grid mesh: domain-
            # decomposed engines step through shard_map(check_rep=False)
            # regions, and without the constraint the partitioner may
            # combine per-device ring updates by SUMMING the row across
            # a mesh axis (observed 2x counts with the snapshot
            # observable's block reshape in the program)
            rep = jax.sharding.NamedSharding(
                built.grid_sharding.mesh, jax.sharding.PartitionSpec())
            inner = hook

            def hook(grid, counts, _inner=inner, _rep=rep):
                return jax.lax.with_sharding_constraint(
                    _inner(grid, counts), _rep)
        built = built._replace(observe=hook)
    return built


# --------------------------- registered engines --------------------------- #

def _pick_sub_batches(n: int, want: int = 8) -> int:
    for d in (want, 4, 2, 1):
        if n % d == 0:
            return d
    return 1


def _tiled_setup(p: "EscgParams"):
    """Shared tile bookkeeping for the sublattice-family engines."""
    th, tw = p.tile
    n_tiles = (p.height // th) * (p.length // tw)
    k_per_tile = max(1, math.ceil(p.n_cells / n_tiles))
    interior = (th - 2) * (tw - 2)
    return th, tw, n_tiles, k_per_tile, interior


def fused_round_inputs(key: jax.Array, th: int, tw: int):
    """Per-MCS (Philox seed words, window shift) schedule of the
    fused-PRNG family: seed = the raw key words, shift keyed by
    ``fold_in(key, 1)``. THE single definition shared by the
    ``pallas_fused`` engine and the sharded engines'
    ``local_kernel='fused'`` path — their bit-identity contract
    (``EngineCaps.equiv_oracles``) depends on there being exactly one."""
    seed = jax.random.key_data(key).astype(jnp.uint32)[-2:]
    shift = round_shift(jax.random.fold_in(key, 1), th, tw)
    return seed, shift


def multi_round_inputs(key: jax.Array, th: int, tw: int, k_steps: int):
    """The K-step fused schedule: ``(key', seeds (K, 2), shifts (K, 2))``.

    Replays EXACTLY the driver's per-MCS key chain — ``key, k1 =
    split(key); fused_round_inputs(k1, ...)`` K times — so a megakernel
    consuming (seeds[t], shifts[t]) at step t is bit-identical to K
    driver-level ``one_mcs`` calls, and the returned key equals the
    driver's key after K MCS (the k_mcs=1 / k_mcs=K equivalence contract).
    ``k_steps`` is a static Python int (one trace per distinct K)."""
    seeds, shifts = [], []
    for _ in range(k_steps):
        key, k1 = jax.random.split(key)
        seed, shift = fused_round_inputs(k1, th, tw)
        seeds.append(seed)
        shifts.append(shift)
    if not seeds:
        return key, jnp.zeros((0, 2), jnp.uint32), jnp.zeros((0, 2),
                                                             jnp.int32)
    return key, jnp.stack(seeds), jnp.stack(shifts)


@register("reference", EngineCaps(
    physics_operand=True,
    description="sequential oracle; one proposal at a time via lax.scan",
    paper="Algorithm 3.2/3.3 (single-threaded baseline)"))
def _build_reference(p: "EscgParams", dom: jax.Array) -> BuiltEngine:
    built_point = physics(p, dom)
    n = p.n_cells

    def one_mcs(grid, key, point=None):
        dom, t_eps, t_eps_mu = built_point if point is None else point
        batch = proposal_batch(key, n, n, p.neighbourhood)
        grid, kept = reference_mod.run_proposals(
            grid, batch, t_eps, t_eps_mu, dom, p.flux)
        return grid, kept, jnp.int32(n)
    return BuiltEngine(one_mcs)


@register("batched", EngineCaps(
    physics_operand=True,
    description="scatter-min conflict arbitration over proposal sub-batches",
    paper="Algorithm 3.5/3.6 (CUDA port, E2)"))
def _build_batched(p: "EscgParams", dom: jax.Array) -> BuiltEngine:
    built_point = physics(p, dom)
    n = p.n_cells
    n_sub = _pick_sub_batches(n)
    b_sub = n // n_sub

    def one_mcs(grid, key, point=None):
        dom, t_eps, t_eps_mu = built_point if point is None else point

        def body(carry, k):
            g, kept = carry
            batch = proposal_batch(k, b_sub, n, p.neighbourhood)
            g, k2 = batched_mod.run_proposals(
                g, batch, t_eps, t_eps_mu, dom, p.flux)
            return (g, kept + k2), None
        keys = jax.random.split(key, n_sub)
        (grid, kept), _ = jax.lax.scan(body, (grid, jnp.int32(0)), keys)
        return grid, kept, jnp.int32(n)
    return BuiltEngine(one_mcs)


def _tiled_one_mcs(p: "EscgParams", run_round):
    """``one_mcs(grid, key, *point)`` of the shifted-window engines (jnp
    and Pallas), around ``run_round(grid, props, shift, *point)``.

    Proposals come from per-tile counter-based streams (tile_stream_batch),
    so the trajectory is a function of (key, tile id) only — the sharded
    engine regenerates identical streams shard-locally and stays
    bit-identical to this single-device path.

    §Perf H3 iter-1: never roll back. Densities / survival statistics are
    translation-invariant on the torus, so the lattice frame is allowed to
    drift by the accumulated shift (composition of uniform shifts stays
    uniform). Halves the roll traffic per round.
    """
    th, tw, n_tiles, k_per_tile, interior = _tiled_setup(p)
    tile_ids = jnp.arange(n_tiles, dtype=jnp.int32)

    def one_mcs(grid, key, *point):
        kp, ks = jax.random.split(key)
        props = tile_stream_batch(kp, tile_ids, k_per_tile, interior,
                                  p.neighbourhood)
        shift = round_shift(ks, th, tw)
        grid = run_round(grid, props, shift, *point)
        attempts = jnp.int32(n_tiles * k_per_tile)
        return grid, attempts, attempts
    return one_mcs


@register("sublattice", EngineCaps(
    flux_only=True, tiled=True, physics_operand=True,
    description="shifted-window synchronous sublattice, pure jnp (E3)",
    paper="maxStep §4.2.4 redesigned for tiles (Fig 4.3)"))
def _build_sublattice(p: "EscgParams", dom: jax.Array) -> BuiltEngine:
    built_point = physics(p, dom)

    def run_round(grid, props, shift, point=None):
        dom, t_eps, t_eps_mu = built_point if point is None else point
        return sublattice_mod.run_round(grid, props, shift, p.tile, t_eps,
                                        t_eps_mu, dom, roll_back=False)
    return BuiltEngine(_tiled_one_mcs(p, run_round))


@register("pallas", EngineCaps(
    flux_only=True, tiled=True, equiv_oracle="sublattice",
    description="sublattice round as a Pallas TPU kernel (VMEM-resident)",
    paper="maxStep §4.2.4, kernelized (Fig 4.3)"))
def _build_pallas(p: "EscgParams", dom: jax.Array) -> BuiltEngine:
    from ..kernels import ops as kernel_ops  # lazy: avoid cycles
    t_eps, t_eps_mu = p.action_thresholds()
    run_round = partial(kernel_ops.escg_round, dom=dom, tile_shape=p.tile,
                        t_eps=t_eps, t_eps_mu=t_eps_mu, roll_back=False)
    return BuiltEngine(_tiled_one_mcs(p, run_round))


@register("pallas_fused", EngineCaps(
    flux_only=True, tiled=True, multi_mcs=True,
    description="Pallas kernel with in-kernel Philox proposal derivation "
                "(zero proposal HBM traffic)",
    paper="numRandoms buffer §3.2.1 eliminated (Fig 4.2)"))
def _build_pallas_fused(p: "EscgParams", dom: jax.Array) -> BuiltEngine:
    from ..kernels import ops as kernel_ops  # lazy: avoid cycles
    t_eps, t_eps_mu = p.action_thresholds()
    th, tw, n_tiles, k_per_tile, _ = _tiled_setup(p)

    def one_mcs(grid, key):
        # per-MCS Philox key = the raw PRNG key words; round_idx = 0
        seed, shift = fused_round_inputs(key, th, tw)
        grid = kernel_ops.escg_round_fused(
            grid, seed, jnp.uint32(0), shift, dom, p.tile, k_per_tile,
            t_eps, t_eps_mu, p.neighbourhood, roll_back=False)
        attempts = jnp.int32(n_tiles * k_per_tile)
        return grid, attempts, attempts

    def multi_mcs(grid, key, k_steps):
        # K MCS per launch: the megakernel consumes the K-step schedule
        # and banks per-step species counts in-kernel
        key, seeds, shifts = multi_round_inputs(key, th, tw, k_steps)
        grid, counts = kernel_ops.escg_rounds_fused(
            grid, seeds, shifts, dom, p.tile, k_per_tile, t_eps, t_eps_mu,
            p.species, p.neighbourhood)
        attempts = jnp.int32(k_steps * n_tiles * k_per_tile)
        return grid, key, counts, attempts, attempts
    return BuiltEngine(one_mcs, multi_mcs=multi_mcs)


@register("sharded", EngineCaps(
    flux_only=True, tiled=True, multi_device=True, vmappable=False,
    trial_shardable=False, mesh_axes=("rows", "cols"),
    local_kernels=("jnp", "pallas", "fused"), multi_mcs=True,
    equiv_oracle="sublattice",
    equiv_oracles=(("fused", "pallas_fused"),),
    description="domain-decomposed across devices: shard_map + ppermute "
                "halo exchange, per-tile Philox streams, psum stasis counts",
    paper="size scaling beyond one device (Fig 4.3, L=3200)"))
def _build_sharded(p: "EscgParams", dom: jax.Array) -> BuiltEngine:
    from . import sharded as sharded_mod  # lazy: pulls parallel/ helpers
    return sharded_mod.build_engine(p, dom)


@register("sharded_pod", EngineCaps(
    flux_only=True, tiled=True, multi_device=True, vmappable=False,
    trial_shardable=False, mesh_axes=("pod", "rows", "cols"),
    local_kernels=("jnp", "pallas", "fused"), multi_mcs=True,
    equiv_oracle="sublattice",
    equiv_oracles=(("fused", "pallas_fused"),),
    description="composed trial x grid mesh: IID trials sharded over "
                "'pod', each lattice halo-exchanged over ('rows','cols'); "
                "same per-tile streams as sharded",
    paper="mass replication of large lattices (Fig 4.3 x Table 4.2)"))
def _build_sharded_pod(p: "EscgParams", dom: jax.Array) -> BuiltEngine:
    from . import sharded_pod as pod_mod  # lazy: pulls parallel/ helpers
    return pod_mod.build_engine(p, dom)
