"""Scenario layer — registry-driven study definitions (DESIGN.md §10).

The paper's platform claim is *configurability*: one simulator replicating
many ESCG studies (classic RPS, Zhong's ablated RPSLS, Park's probabilistic
eight-species alliances, parametric N-species cycles). ``EscgParams``
conflates three concerns — WHAT is simulated, HOW one MCS is computed, and
HOW LONG / WHERE the run happens — so every new study meant hand-editing
drivers. This module decomposes the config API into three composable frozen
dataclasses:

* :class:`Scenario` — the physics of one study: species count, dominance
  network, action rates (mu / sigma / epsilon), boundary condition,
  neighbourhood, initial-condition knobs. Presets register in a first-class
  registry (``@register_scenario`` + :class:`ScenarioCaps` capability
  metadata), exactly mirroring the engine registry in ``engines.py``:
  the CLI (``--scenario NAME``, ``--listScenarios``), the README scenario
  matrix and the validation layer all resolve scenarios through this table.
* :class:`EngineConfig` — engine selection: engine name, tile, cell dtype,
  device layouts (``shard_grid`` / ``mesh_shape``), local kernel.
* :class:`RunConfig` — run control: lattice size, MCS budget, chunking,
  seed, output/IO knobs.

``compose(scenario, engine, run)`` assembles the three into the legacy
``EscgParams`` (the back-compat facade — bit-identical trajectories, JSON
round-trip preserved); ``decompose(params)`` inverts it. Parametric
families resolve by name suffix: ``make_scenario("nspecies7")`` is the
7-species cyclic game.
"""
from __future__ import annotations

import dataclasses
import hashlib
import inspect
import json
import re
from dataclasses import dataclass, field
from typing import Callable, Dict, Mapping, Optional, Sequence, Tuple, Union

import numpy as np

from . import dominance as dom_mod
from .engines import get_engine, physics
from .params import EscgParams, parse_observables

__all__ = [
    "Scenario", "ScenarioCaps", "ScenarioSpec", "EngineConfig", "RunConfig",
    "register_scenario", "scenario_names", "scenario_specs", "get_scenario",
    "make_scenario", "scenario_key", "compose", "decompose",
    "resolve_config", "resolve_points", "POINT_FIELDS",
    "scenario_from_cli", "engine_config_from_args", "run_config_from_args",
    "SCENARIO_CLI_FIELDS",
]

BOUNDARIES = ("flux", "reflect")   # periodic torus | reflecting walls


def _freeze_extras(extras) -> Tuple[Tuple[str, float], ...]:
    items = extras.items() if isinstance(extras, Mapping) else extras
    return tuple(sorted((str(k), float(v)) for k, v in items))


# ------------------------------- Scenario --------------------------------- #

@dataclass(frozen=True)
class Scenario:
    """WHAT is simulated — the physics of one ESCG study.

    Pure data (JSON round-trippable): the dominance network is *derived*,
    not stored — :meth:`dominance` dispatches on ``name`` through the
    scenario registry, so a ``Scenario`` parsed back from JSON rebuilds
    exactly the matrix its preset defines. Ad-hoc scenarios (empty or
    unregistered ``name``) fall back to the legacy default, the circulant
    ``C(S, {1})`` cycle — the same default ``simulate`` applies when called
    with ``dom=None``.
    """
    name: str = ""                 # registry name ('' = ad-hoc / legacy)
    species: int = 3
    neighbourhood: int = 4         # 4 = von Neumann, 8 = Moore
    mobility: float = 3e-5         # M: typical area explored per unit time
    mu: float = 1.0                # interaction rate
    sigma: float = 1.0             # reproduction rate
    epsilon: Optional[float] = None  # migration; None = 2*M*N (paper)
    boundary: str = "flux"         # 'flux' (periodic torus) | 'reflect'
    empty: float = 0.0             # initial empty-cell probability
    # preset-specific knobs (e.g. Park's alpha/beta/gamma), stored sorted
    # so equal scenarios compare equal
    extras: Tuple[Tuple[str, float], ...] = ()

    def __post_init__(self):
        # normalize extras in the constructor itself: a dict (arbitrary
        # iteration order) or an unsorted tuple would otherwise produce a
        # Scenario that compares unequal to — and content-hashes
        # differently from (scenario_key) — the same physics built sorted.
        object.__setattr__(self, "extras", _freeze_extras(self.extras))

    @property
    def flux(self) -> bool:
        return self.boundary == "flux"

    def extra(self, key: str, default: Optional[float] = None) -> float:
        for k, v in self.extras:
            if k == key:
                return v
        if default is None:
            raise KeyError(f"scenario {self.name!r} has no extra {key!r}")
        return float(default)

    def validate(self) -> "Scenario":
        if self.boundary not in BOUNDARIES:
            raise ValueError(f"boundary must be one of {BOUNDARIES}, "
                             f"got {self.boundary!r}")
        if self.species < 1:
            raise ValueError("species >= 1")
        if self.neighbourhood not in (4, 8):
            raise ValueError("neighbourhood must be 4 or 8")
        if not (0.0 <= self.empty <= 1.0):
            raise ValueError("empty in [0,1]")
        spec = _spec_for(self.name)
        if spec is not None and spec.caps.species is not None \
                and self.species != spec.caps.species:
            raise ValueError(
                f"scenario {self.name!r} is a fixed {spec.caps.species}-"
                f"species study; cannot override species={self.species}")
        return self

    def dominance(self) -> np.ndarray:
        """The (S+1, S+1) dominance network of this scenario, rebuilt from
        the registry spec (or the legacy circulant default when ad-hoc)."""
        spec = _spec_for(self.name)
        if spec is not None and spec.dominance is not None:
            return spec.dominance(self)
        return dom_mod.circulant(self.species)

    def to_legacy(self, engine: Optional["EngineConfig"] = None,
                  run: Optional["RunConfig"] = None) -> EscgParams:
        """Compose into the back-compat ``EscgParams`` facade."""
        return compose(self, engine, run)

    def replace(self, **kw) -> "Scenario":
        if "extras" in kw:
            kw["extras"] = _freeze_extras(kw["extras"])
        return dataclasses.replace(self, **kw)

    # ------------------------------ io -------------------------------- #
    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))

    @staticmethod
    def from_json(s: str) -> "Scenario":
        d = json.loads(s)
        d["extras"] = _freeze_extras(d.get("extras", ()))
        return Scenario(**d)


# ------------------------- EngineConfig / RunConfig ------------------------ #

@dataclass(frozen=True)
class EngineConfig:
    """HOW one MCS is computed — engine selection and device layout.

    Mirrors the TPU-adaptation block of ``EscgParams``; legality of every
    knob is still decided by the engine registry (``EngineCaps``) when the
    config is composed and validated."""
    engine: str = "batched"
    cell_dtype: str = "int32"
    tile: Tuple[int, int] = (8, 32)
    shard_grid: Optional[Tuple[int, int]] = None
    mesh_shape: Optional[Tuple[int, int, int]] = None
    local_kernel: str = "jnp"
    # MCS fused per kernel launch (multi-MCS megakernel; fused-Philox
    # engines only — see EscgParams.k_mcs)
    k_mcs: int = 1

    def replace(self, **kw) -> "EngineConfig":
        return dataclasses.replace(self, **kw)

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))

    @staticmethod
    def from_json(s: str) -> "EngineConfig":
        d = json.loads(s)
        d["tile"] = tuple(d["tile"])
        for k in ("shard_grid", "mesh_shape"):
            if d.get(k) is not None:
                d[k] = tuple(d[k])
        return EngineConfig(**d)


@dataclass(frozen=True)
class RunConfig:
    """HOW LONG / WHERE — run control, lattice extent and IO."""
    length: int = 200
    height: int = 200
    mcs: int = 100_000
    chunk_mcs: int = 100
    seed: int = 0
    print_frequency: int = 200
    num_randoms: int = 0
    max_step: bool = False
    save: bool = False
    resume: bool = False
    out_dir: str = "escg_out"
    # streaming observables (DESIGN.md §11): None = defer to the
    # scenario's ScenarioCaps.observables (filled by resolve_config on
    # scenario-first driver calls); () = explicitly off; a tuple of
    # registered names selects exactly those.
    observables: Optional[Tuple[str, ...]] = None
    obs_capacity: int = 0          # ring rows; 0 = auto (one chunk)

    def replace(self, **kw) -> "RunConfig":
        return dataclasses.replace(self, **kw)

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))

    @staticmethod
    def from_json(s: str) -> "RunConfig":
        d = json.loads(s)
        if d.get("observables") is not None:
            d["observables"] = tuple(d["observables"])
        return RunConfig(**d)


# ------------------------------- registry ---------------------------------- #

@dataclass(frozen=True)
class ScenarioCaps:
    """Static capability metadata, consumed by validation, the CLI scenario
    matrix and the docs (mirror of ``EngineCaps``)."""
    species: Optional[int] = None  # fixed species count; None = parametric
    rates: str = "deterministic"   # dominance entries: {0,1} or [0,1] rates
    boundary: str = "flux"         # boundary condition the study assumes
    init: str = "uniform"          # initial-condition sampler family
    observables: Tuple[str, ...] = ()  # the statistics the study reads
    description: str = ""
    paper: str = ""                # study / figure the preset reproduces


@dataclass(frozen=True)
class ScenarioSpec:
    name: str
    caps: ScenarioCaps
    build: Callable[..., Scenario] = field(repr=False, default=None)
    # dominance(scenario) -> (S+1, S+1) float32; None = circulant default
    dominance: Optional[Callable[[Scenario], np.ndarray]] = field(
        repr=False, default=None)


_REGISTRY: Dict[str, ScenarioSpec] = {}


def register_scenario(name: str, caps: ScenarioCaps,
                      dominance: Optional[Callable[[Scenario], np.ndarray]]
                      = None):
    """Decorator: register ``build(**overrides) -> Scenario`` under
    ``name``. Re-registration replaces (same contract as engines)."""
    def deco(build_fn):
        _REGISTRY[name] = ScenarioSpec(name=name, caps=caps, build=build_fn,
                                       dominance=dominance)
        return build_fn
    return deco


def scenario_names() -> Tuple[str, ...]:
    return tuple(_REGISTRY)


def scenario_specs() -> Tuple[ScenarioSpec, ...]:
    return tuple(_REGISTRY.values())


_PARAMETRIC = re.compile(r"^([A-Za-z_]+?)(\d+)$")


def _resolve_name(name: str):
    """(spec, extra_kwargs) for ``name`` — parametric families resolve by
    suffix: 'nspecies7' -> the 'nspecies' family with S=7."""
    if name in _REGISTRY:
        return _REGISTRY[name], {}
    m = _PARAMETRIC.match(name)
    if m and m.group(1) in _REGISTRY \
            and _REGISTRY[m.group(1)].caps.species is None:
        return _REGISTRY[m.group(1)], {"S": int(m.group(2))}
    raise ValueError(
        f"unknown scenario {name!r}; registered: {scenario_names()} "
        "(parametric families accept a numeric suffix, e.g. 'nspecies7')")


def _spec_for(name: str) -> Optional[ScenarioSpec]:
    """Registry spec for ``name``, or None for ad-hoc scenarios."""
    if not name:
        return None
    try:
        return _resolve_name(name)[0]
    except ValueError:
        return None


def get_scenario(name: str) -> ScenarioSpec:
    return _resolve_name(name)[0]


def make_scenario(name: str, **overrides) -> Scenario:
    """Build a registered scenario preset. ``overrides`` route by name:
    knobs the builder declares (e.g. ``alpha=`` for 'probabilistic') go to
    the builder — preserving preset-internal coupling like Park's
    mobility->epsilon rule — and plain ``Scenario`` field names are
    applied on top of the built preset."""
    spec, kw = _resolve_name(name)
    accepts = {p.name for p in inspect.signature(spec.build)
               .parameters.values()
               if p.kind in (p.POSITIONAL_OR_KEYWORD, p.KEYWORD_ONLY)}
    field_names = {f.name for f in dataclasses.fields(Scenario)}
    build_kw, field_kw = {}, {}
    for k, v in overrides.items():
        if k in accepts:
            build_kw[k] = v
        elif k in field_names:
            field_kw[k] = v
        else:
            raise ValueError(
                f"scenario {name!r} accepts builder knobs {sorted(accepts)}"
                f" and Scenario fields {sorted(field_names)}; got {k!r}")
    sc = spec.build(**kw, **build_kw)
    if field_kw:
        sc = sc.replace(**field_kw)
    return sc.validate()


# ------------------------------ composition -------------------------------- #

def compose(scenario: Scenario, engine: Optional[EngineConfig] = None,
            run: Optional[RunConfig] = None) -> EscgParams:
    """Assemble (Scenario, EngineConfig, RunConfig) into a validated
    ``EscgParams`` — the back-compat facade every driver still consumes.

    Boundary legality is checked here with NAMES on both sides: a
    ``flux_only`` engine (see ``EngineCaps``) cannot run a reflecting
    scenario, and the error says which scenario met which engine instead
    of the facade's anonymous flux complaint."""
    engine = engine or EngineConfig()
    run = run or RunConfig()
    scenario = scenario.validate()
    ecaps = get_engine(engine.engine).caps
    if ecaps.flux_only and not scenario.flux:
        raise ValueError(
            f"scenario {scenario.name or '<ad-hoc>'!r} uses reflecting "
            f"boundaries (boundary='reflect') but engine "
            f"{engine.engine!r} is flux-only (periodic torus); run it on a "
            "boundary-agnostic engine such as 'reference' or 'batched', "
            "or set boundary='flux'")
    return EscgParams(
        length=run.length, height=run.height, mcs=run.mcs,
        neighbourhood=scenario.neighbourhood,
        print_frequency=run.print_frequency, mobility=scenario.mobility,
        species=scenario.species, flux=scenario.flux, empty=scenario.empty,
        save=run.save, resume=run.resume, num_randoms=run.num_randoms,
        max_step=run.max_step, mu=scenario.mu, sigma=scenario.sigma,
        epsilon=scenario.epsilon, engine=engine.engine,
        cell_dtype=engine.cell_dtype, tile=engine.tile, seed=run.seed,
        chunk_mcs=run.chunk_mcs, out_dir=run.out_dir,
        shard_grid=engine.shard_grid, mesh_shape=engine.mesh_shape,
        local_kernel=engine.local_kernel, k_mcs=engine.k_mcs,
        observables=(() if run.observables is None
                     else tuple(run.observables)),
        obs_capacity=run.obs_capacity).validate()


def decompose(params: EscgParams, name: str = ""
              ) -> Tuple[Scenario, EngineConfig, RunConfig]:
    """Invert :func:`compose`: split a flat ``EscgParams`` into the three
    layers. ``compose(*decompose(p)) == p`` for every valid ``p``."""
    sc = Scenario(
        name=name, species=params.species,
        neighbourhood=params.neighbourhood, mobility=params.mobility,
        mu=params.mu, sigma=params.sigma, epsilon=params.epsilon,
        boundary="flux" if params.flux else "reflect", empty=params.empty)
    eng = EngineConfig(
        engine=params.engine, cell_dtype=params.cell_dtype,
        tile=params.tile, shard_grid=params.shard_grid,
        mesh_shape=params.mesh_shape, local_kernel=params.local_kernel,
        k_mcs=params.k_mcs)
    run = RunConfig(
        length=params.length, height=params.height, mcs=params.mcs,
        chunk_mcs=params.chunk_mcs, seed=params.seed,
        print_frequency=params.print_frequency,
        num_randoms=params.num_randoms, max_step=params.max_step,
        save=params.save, resume=params.resume, out_dir=params.out_dir,
        observables=tuple(params.observables),
        obs_capacity=params.obs_capacity)
    return sc, eng, run


def resolve_config(params: Union[EscgParams, Scenario],
                   dom: Optional[np.ndarray] = None,
                   engine_config: Optional[EngineConfig] = None,
                   run_config: Optional[RunConfig] = None):
    """Normalize a driver's config input to ``(EscgParams, dom)``.

    Drivers (``simulate``, ``run_trials``, ``engines.build``) accept either
    the legacy facade or a :class:`Scenario` (+ optional engine/run
    configs). For scenarios with ``dom=None`` the dominance network comes
    from the registry — the study carries its own physics.

    Scenario-first calls additionally make ``ScenarioCaps.observables``
    load-bearing (DESIGN.md §11): unless the ``RunConfig`` pins
    ``observables`` (a tuple, ``()`` = explicitly off), the composed
    params stream the preset's declared observables — filtered to names
    the observable registry actually implements (caps also list
    result-level statistics like ``survival`` that are not streaming
    observables)."""
    if isinstance(params, Scenario):
        if dom is None:
            dom = params.dominance()
        composed = compose(params, engine_config, run_config)
        if run_config is None or run_config.observables is None:
            obs = scenario_observables(params.name)
            if obs:
                composed = composed.replace(observables=obs).validate()
        return composed, dom
    if engine_config is not None or run_config is not None:
        raise ValueError(
            "engine_config/run_config only apply when the first argument "
            "is a Scenario; an EscgParams already carries both layers")
    return params, dom


# the fields in which the points of one trial batch may differ: the
# action rates, besides the dominance network each point's scenario derives
POINT_FIELDS = ("mobility", "mu", "sigma", "epsilon")


def resolve_points(scenarios: Sequence[Union[EscgParams, Scenario]],
                   dom: Optional[np.ndarray] = None,
                   engine_config: Optional[EngineConfig] = None,
                   run_config: Optional[RunConfig] = None):
    """``(EscgParams, [Physics, ...])`` of a sweep: each scenario composed
    with the shared engine and run configs by :func:`resolve_config`
    (the dominance network ``dom`` for every point, else each scenario's
    own from the registry), then checked to share every field that sets
    the shape of the batch: species, neighbourhood, boundary, empty
    fraction, sizes, dtype, engine and the rest, all but
    ``POINT_FIELDS``. A mismatch raises a ``ValueError`` naming the
    field. The params returned are the first point's; the list holds one
    ``engines.Physics`` per scenario, in order."""
    if not scenarios:
        raise ValueError("a sweep needs at least one scenario")
    resolved = []
    for sc in scenarios:
        params, dom_p = resolve_config(sc, dom, engine_config, run_config)
        params = params.validate()
        if dom_p is None:
            dom_p = dom_mod.circulant(params.species)
        resolved.append((params, dom_p))
    first = resolved[0][0]
    blank = dict.fromkeys(POINT_FIELDS)
    for params, _ in resolved[1:]:
        a, b = first.replace(**blank), params.replace(**blank)
        for f in dataclasses.fields(EscgParams):
            if getattr(a, f.name) != getattr(b, f.name):
                raise ValueError(
                    f"the points of a sweep must share every field that "
                    f"sets the batch's shape; they differ in {f.name!r}: "
                    f"{getattr(a, f.name)!r} and {getattr(b, f.name)!r}")
    return first, [physics(params, dom) for params, dom in resolved]


def scenario_key(scenario: Scenario) -> str:
    """Stable content hash of a scenario's physics (DESIGN.md §12).

    The serving layer's compiled-engine cache keys on this: two requests
    share a compiled program only when every physics field — species,
    neighbourhood, rates, boundary, init occupancy, preset extras, and
    the registry name the dominance network derives from — is identical.
    The hash is canonical-JSON (sorted keys, normalized extras) over the
    dataclass fields, so it is reproducible across processes and Python
    hash seeds; never Python ``hash()`` (PYTHONHASHSEED-dependent).
    Floats serialize via ``repr`` (shortest round-trip), so equal values
    hash equal on every platform JAX supports."""
    d = dataclasses.asdict(scenario)
    # asdict keeps the (already sorted — Scenario.__post_init__) extras
    # tuple; JSON encodes it as nested lists, canonically
    payload = json.dumps(d, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(payload.encode("utf-8")).hexdigest()[:16]


def scenario_observables(name: str) -> Tuple[str, ...]:
    """The streaming subset of a scenario's ``ScenarioCaps.observables``
    (DESIGN.md §11): declared names that resolve in the observable
    registry, in declaration order. Caps may also declare result-level
    statistics (``survival``, ``stasis_mcs``, ...) — those are computed
    by the drivers from the same streams, not registered as device
    observables, and are filtered out here. Ad-hoc scenarios: ()."""
    from . import observables as obs_mod  # lazy: keep import graph acyclic
    spec = _spec_for(name)
    if spec is None:
        return ()
    registered = set(obs_mod.observable_names())
    return tuple(o for o in spec.caps.observables if o in registered)


# ------------------------------ CLI bridging ------------------------------- #

# Scenario-owned CLI fields: with --scenario these come from the preset
# unless the flag is explicitly given (detected as differing from the
# argparse default — a user re-passing the exact default defers to the
# preset, which is the documented behaviour).
SCENARIO_CLI_FIELDS = ("species", "neighbourhood", "mobility", "mu",
                       "sigma", "epsilon", "empty", "flux")


def scenario_from_cli(args, parser) -> Scenario:
    """Build the ``--scenario`` preset, overridden by explicitly-passed
    scenario-owned CLI flags (see ``SCENARIO_CLI_FIELDS``). ``parser`` is
    required: its defaults are how "explicitly passed" is detected —
    without it every argparse default would silently override the
    preset's physics."""
    sc = make_scenario(args.scenario)
    over = {}
    for f in SCENARIO_CLI_FIELDS:
        v = getattr(args, f, None)
        if v is None or v == parser.get_default(f):
            continue
        over[f] = v
    if "flux" in over:
        over["boundary"] = "flux" if over.pop("flux") else "reflect"
    return sc.replace(**over).validate() if over else sc


def engine_config_from_args(args) -> EngineConfig:
    kw = {}
    for f in dataclasses.fields(EngineConfig):
        v = getattr(args, f.name, None)
        if v is not None:
            kw[f.name] = tuple(v) if isinstance(v, list) else v
    return EngineConfig(**kw)


def run_config_from_args(args) -> RunConfig:
    kw = {}
    for f in dataclasses.fields(RunConfig):
        v = getattr(args, f.name, None)
        if v is not None:
            kw[f.name] = v
    if "observables" in kw:
        # the CLI carries a comma-separated string; None (flag absent)
        # never lands here, so absent keeps the defer-to-scenario default
        kw["observables"] = parse_observables(kw["observables"])
    return RunConfig(**kw)


# ------------------------------ presets ------------------------------------ #
# The paper's study space (§3.1, §4.3): each preset is one published ESCG
# study, reproduced end-to-end by composing it with any engine/run config.

@register_scenario("park3", ScenarioCaps(
    species=3, rates="deterministic",
    observables=("densities", "interface_length", "stasis_mcs"),
    description="paper baseline rock-paper-scissors: cyclic C(3,{1}) "
                "dominance at low mobility (RMF spiral regime)",
    paper="Tables 3.1/3.2; Reichenbach-Mobilia-Frey Fig 1.1"),
    dominance=lambda sc: dom_mod.RPS())
def _build_park3() -> Scenario:
    return Scenario(name="park3", species=3, mobility=3e-5)


@register_scenario("zhong_density", ScenarioCaps(
    species=5, rates="deterministic",
    observables=("extinction_mcs", "densities"),
    description="Zhong et al. (2022) ablated RPSLS: the Rock-crushes-"
                "Scissors edge removed; Paper goes extinct in 200-600 MCS",
    paper="paper §3.1.2, Figs 3.2/3.3 (Zhong Fig 2)"),
    dominance=lambda sc: dom_mod.zhong_ablated_rpsls())
def _build_zhong_density() -> Scenario:
    return Scenario(name="zhong_density", species=5, mobility=1e-4)


def _nspecies_dom(sc: Scenario) -> np.ndarray:
    # canonical cyclic family: C(S,{1,2}) from 5 species up (RPSLS and
    # its generalizations), C(S,{1}) below — the same rule the CLI default
    # applies
    offs = (1, 2) if sc.species >= 5 else (1,)
    return dom_mod.circulant(sc.species, offs)


@register_scenario("nspecies", ScenarioCaps(
    species=None, rates="deterministic",
    observables=("densities", "survival"),
    description="parametric S-species cyclic game: C(S,{1,2}) for S >= 5 "
                "(RPSLS family), C(S,{1}) below; name suffix sets S "
                "('nspecies7')",
    paper="paper §3.1.1 circulant C(S,K) family"),
    dominance=_nspecies_dom)
def _build_nspecies(S: int = 5) -> Scenario:
    if S < 1:
        raise ValueError("nspecies family needs S >= 1")
    return Scenario(name=f"nspecies{S}", species=S, mobility=3e-5)


def _park_alliance_dom(sc: Scenario) -> np.ndarray:
    return dom_mod.park_alliance_network(
        sc.extra("alpha"), sc.extra("beta"), sc.extra("gamma"))


@register_scenario("probabilistic", ScenarioCaps(
    species=8, rates="probabilistic",
    observables=("survival", "survivors_hist", "extinction_mcs"),
    description="Park, Chen & Szolnoki (2023) eight-species alliances: "
                "probabilistic (alpha, beta, gamma) rates, no migration, "
                "terminate after L^2 MCS",
    paper="paper §4.3.2, Figs 4.9-4.13, Table 4.2"),
    dominance=_park_alliance_dom)
def _build_probabilistic(alpha: float = 0.15, beta: float = 0.75,
                         gamma: float = 1.0,
                         mobility: float = 0.0) -> Scenario:
    # Park et al. have no migration; the companion paper's extension is
    # mobility > 0 (then epsilon reverts to the 2*M*N default)
    return Scenario(name="probabilistic", species=8, mobility=mobility,
                    epsilon=None if mobility > 0 else 0.0,
                    extras=_freeze_extras(
                        {"alpha": alpha, "beta": beta, "gamma": gamma}))


def _asym_dom(sc: Scenario) -> np.ndarray:
    r12, r23, r31 = (sc.extra("r12"), sc.extra("r23"), sc.extra("r31"))
    return dom_mod.from_dense(np.array([[0.0, r12, 0.0],
                                        [0.0, 0.0, r23],
                                        [r31, 0.0, 0.0]], dtype=np.float32))


@register_scenario("asym_rps", ScenarioCaps(
    species=3, rates="probabilistic",
    observables=("densities", "survival"),
    description="asymmetric-dominance RPS: the three cyclic edges carry "
                "unequal kill rates (r12, r23, r31) — breaks the "
                "symmetric-coexistence degeneracy",
    paper="paper §3.1.1 rate generalization (Park-style asymmetry)"),
    dominance=_asym_dom)
def _build_asym_rps(r12: float = 1.0, r23: float = 0.7,
                    r31: float = 0.4) -> Scenario:
    return Scenario(name="asym_rps", species=3, mobility=3e-5,
                    extras=_freeze_extras(
                        {"r12": r12, "r23": r23, "r31": r31}))
