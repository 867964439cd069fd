"""Simulation parameters — CLI-parity with the paper's Tables 3.1 and 3.2.

The paper exposes a single configurable simulator; we mirror every flag
(``--length``, ``--height``, ``--mcs``, ``--neighbourhood``, ``--mobility``,
``--species``, ``--flux``, ``--empty``, ``--save``, ``--dominance``,
``--resume``, ``--numRandoms``, ``--maxStep``) plus engine-selection knobs
introduced by the TPU adaptation (see DESIGN.md §2).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import re
from dataclasses import dataclass
from typing import Optional, Tuple

from .engines import engine_names, validate_params as _validate_engine
from .rules import quantize_rate


def __getattr__(name: str):
    # Back-compat `params.ENGINES` alias (DESIGN.md §2). A module-level
    # constant would snapshot engine_names() at import time and go stale
    # after late @register calls (notebooks, tests, plugins); deferring to
    # the registry through the module __getattr__ keeps it live.
    if name == "ENGINES":
        return engine_names()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


@dataclass(frozen=True)
class EscgParams:
    # ---- paper Table 3.1 ----
    length: int = 200              # lattice width  W
    height: int = 200              # lattice height H
    mcs: int = 100_000             # Monte Carlo step limit
    neighbourhood: int = 4         # 4 = von Neumann, 8 = Moore
    print_frequency: int = 200     # density print interval (MCS)
    mobility: float = 3e-5         # M: typical area explored per unit time
    species: int = 3
    flux: bool = True              # periodic (wrap) boundary; False = reflect
    empty: float = 0.0             # initial empty-cell probability
    save: bool = False             # export snapshots/state
    # ---- paper Table 3.2 (GPU extensions) ----
    resume: bool = False
    num_randoms: int = 0           # proposals per round; 0 -> N (one MCS/round)
    max_step: bool = False         # multiple MCS per round (maxStep mode)
    # ---- action rates (paper §3.1.1) ----
    mu: float = 1.0                # interaction
    sigma: float = 1.0             # reproduction
    epsilon: Optional[float] = None  # migration; default 2*M*N (paper)
    # ---- TPU adaptation knobs ----
    engine: str = "batched"        # any registered engine (engines.py)
    cell_dtype: str = "int32"      # int8 quarters lattice HBM traffic
    tile: Tuple[int, int] = (8, 32)   # sublattice tile (th, tw)
    seed: int = 0
    chunk_mcs: int = 100           # MCS per jitted chunk (device-resident loop)
    out_dir: str = "escg_out"
    # sharded engine: (rows, cols) device grid; None = auto-factor all
    # local devices (parallel.sharding.auto_shard_grid)
    shard_grid: Optional[Tuple[int, int]] = None
    # sharded_pod engine: (pod, rows, cols) composed device mesh — the
    # trial axis shards over 'pod' while each trial's lattice is
    # domain-decomposed over ('rows','cols'); None = all local devices on
    # the pod axis (DESIGN.md §6). Which layouts are legal is decided by
    # the engine's EngineCaps.mesh_axes, not by the drivers.
    mesh_shape: Optional[Tuple[int, int, int]] = None
    # tile sweep implementation inside the sharded engines' shard_map
    # region: 'jnp' (vmapped lax.scan sweeps), 'pallas' (the VMEM-tiled
    # kernels.escg_update path, bit-identical to 'jnp'), or 'fused'
    # (in-kernel Philox proposal derivation keyed by global tile identity
    # — zero proposal HBM traffic, bit-identical to engine='pallas_fused')
    local_kernel: str = "jnp"
    # Monte-Carlo steps per kernel launch (the multi-MCS megakernel,
    # DESIGN.md §6): k_mcs > 1 runs K steps grid-resident per pallas_call,
    # amortizing launch overhead and HBM round-trips K×. Fused-Philox
    # family only (engine pallas_fused, or sharded/sharded_pod with
    # local_kernel='fused'); bit-identical to k_mcs=1 by construction.
    k_mcs: int = 1
    # streaming observables evaluated inside the jitted engine step and
    # ring-buffered in device memory (DESIGN.md §11); () = off (legacy
    # per-chunk counts transfer). Names resolve through the observable
    # registry (core/observables.py); scenario-first driver calls fill
    # this from ScenarioCaps.observables.
    observables: Tuple[str, ...] = ()
    # ring-buffer row capacity; 0 = auto (one chunk of rows, lossless).
    # The trial driver tolerates smaller capacities (lossy wraparound);
    # simulate requires capacity >= chunk_mcs (its stasis accounting
    # reads the flushed rows).
    obs_capacity: int = 0

    # ------------------------------------------------------------------ #
    @property
    def n_cells(self) -> int:
        return self.length * self.height

    @property
    def eps(self) -> float:
        if self.epsilon is not None:
            return float(self.epsilon)
        return 2.0 * self.mobility * self.n_cells

    def action_thresholds(self) -> Tuple[float, float]:
        """Normalized cumulative thresholds (t_eps, t_eps_mu) on u ~ U[0,1).

        u <  t_eps          -> migration
        u <  t_eps_mu       -> interaction
        else                -> reproduction
        (paper Algorithm 3.2 ordering), as the float32 values the device
        compares (``rules.quantize_rate``)
        """
        total = self.mu + self.sigma + self.eps
        if total <= 0:
            raise ValueError("mu + sigma + epsilon must be positive")
        return (quantize_rate(self.eps / total),
                quantize_rate((self.eps + self.mu) / total))

    @property
    def proposals_per_round(self) -> int:
        n = self.num_randoms if self.num_randoms > 0 else self.n_cells
        if not self.max_step:
            n = min(n, self.n_cells)
        # paper: numRandoms = (numRandoms / N) * N  (align with whole MCS)
        n = max(self.n_cells, (n // self.n_cells) * self.n_cells)
        return n

    @property
    def mcs_per_round(self) -> int:
        return self.proposals_per_round // self.n_cells

    def validate(self) -> "EscgParams":
        if self.neighbourhood not in (4, 8):
            raise ValueError("neighbourhood must be 4 or 8")
        if self.species < 1:
            raise ValueError("species >= 1")
        if not (0.0 <= self.empty <= 1.0):
            raise ValueError("empty in [0,1]")
        if self.length < 3 or self.height < 3:
            raise ValueError("lattice must be at least 3x3")
        if self.cell_dtype not in ("int8", "int16", "int32"):
            raise ValueError("cell_dtype must be int8/int16/int32")
        if self.cell_dtype == "int8" and self.species > 127:
            raise ValueError("int8 lattice supports <= 127 species")
        # engine existence + capability checks (flux, tile, devices) live
        # with the registry so new engines carry their own constraints
        _validate_engine(self)
        return self

    # ------------------------------ io -------------------------------- #
    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))

    @staticmethod
    def from_json(s: str) -> "EscgParams":
        d = json.loads(s)
        d["tile"] = tuple(d["tile"])
        if d.get("shard_grid") is not None:
            d["shard_grid"] = tuple(d["shard_grid"])
        if d.get("mesh_shape") is not None:
            d["mesh_shape"] = tuple(d["mesh_shape"])
        if d.get("observables") is not None:
            d["observables"] = tuple(d["observables"])
        return EscgParams(**d)

    def replace(self, **kw) -> "EscgParams":
        return dataclasses.replace(self, **kw)

    # -------------------- scenario-layer facade ----------------------- #
    @classmethod
    def from_scenario(cls, scenario, engine_config=None,
                      run_config=None) -> "EscgParams":
        """Compose a ``Scenario`` (+ optional ``EngineConfig`` /
        ``RunConfig``) into the legacy flat params — the back-compat
        facade over the scenario layer (DESIGN.md §10). Bit-identical to
        hand-building the same ``EscgParams``."""
        from .scenarios import compose  # lazy: scenarios imports us
        return compose(scenario, engine_config, run_config)

    def to_scenario(self, name: str = ""):
        """Decompose into ``(Scenario, EngineConfig, RunConfig)``;
        ``EscgParams.from_scenario(*p.to_scenario()) == p``."""
        from .scenarios import decompose  # lazy: scenarios imports us
        return decompose(self, name=name)


def _mesh_shape(s: str) -> Tuple[int, int, int]:
    """Parse ``--meshShape P,R,C`` (also accepts 'PxRxC')."""
    parts = [x for x in re.split(r"[,x]", s) if x]
    if len(parts) != 3:
        raise argparse.ArgumentTypeError(
            f"meshShape must be P,R,C (three ints), got {s!r}")
    return tuple(int(x) for x in parts)


def add_cli_args(p: argparse.ArgumentParser) -> None:
    b = lambda s: s.lower() in ("1", "true", "yes")  # noqa: E731
    p.add_argument("--length", type=int, default=200)
    p.add_argument("--height", type=int, default=200)
    p.add_argument("--mcs", type=int, default=100_000)
    p.add_argument("--neighbourhood", type=int, default=4, choices=(4, 8))
    p.add_argument("--printFrequency", dest="print_frequency", type=int,
                   default=200)
    p.add_argument("--mobility", type=float, default=3e-5)
    p.add_argument("--species", type=int, default=3)
    p.add_argument("--flux", type=b, default=True)
    p.add_argument("--empty", type=float, default=0.0)
    p.add_argument("--save", type=b, default=False)
    p.add_argument("--dominance", type=str, default="",
                   help="path to dominance .csv (paper --dominance)")
    p.add_argument("--resume", type=b, default=False)
    p.add_argument("--numRandoms", dest="num_randoms", type=int, default=0)
    p.add_argument("--maxStep", dest="max_step", type=b, default=False)
    p.add_argument("--mu", type=float, default=1.0)
    p.add_argument("--sigma", type=float, default=1.0)
    p.add_argument("--epsilon", type=float, default=None)
    p.add_argument("--engine", type=str, default="batched",
                   choices=engine_names())
    p.add_argument("--cellDtype", dest="cell_dtype", type=str,
                   default="int32", choices=("int8", "int16", "int32"))
    p.add_argument("--tile", type=int, nargs=2, default=(8, 32))
    p.add_argument("--shardGrid", dest="shard_grid", type=int, nargs=2,
                   default=None,
                   help="(rows, cols) device grid for engine=sharded; "
                        "omit to auto-factor all local devices")
    p.add_argument("--meshShape", dest="mesh_shape", type=_mesh_shape,
                   default=None, metavar="P,R,C",
                   help="composed (pod, rows, cols) device mesh for "
                        "engine=sharded_pod: --trials shard over the pod "
                        "axis, each lattice over (rows, cols); omit to put "
                        "all local devices on the pod axis")
    p.add_argument("--localKernel", dest="local_kernel", type=str,
                   default="jnp", choices=("jnp", "pallas", "fused"),
                   help="tile-sweep implementation inside the sharded "
                        "engines' shard_map region: jnp and pallas are "
                        "bit-identical to each other; fused derives "
                        "proposals in-kernel from Philox counters (zero "
                        "proposal HBM traffic, bit-identical to "
                        "--engine pallas_fused)")
    p.add_argument("--kMcs", dest="k_mcs", type=int, default=1,
                   help="Monte-Carlo steps fused into one kernel launch "
                        "(the multi-MCS megakernel; fused-Philox engines "
                        "only, bit-identical to --kMcs 1)")
    p.add_argument("--observables", type=str, default=None,
                   help="comma-separated streaming observables computed "
                        "on-device and ring-buffered (DESIGN.md §11), "
                        "e.g. 'densities,interface_length'; 'none' "
                        "disables; default: off (with --scenario, the "
                        "preset's ScenarioCaps.observables)")
    p.add_argument("--obsCapacity", dest="obs_capacity", type=int,
                   default=0,
                   help="observable ring-buffer capacity in rows; 0 = "
                        "auto (one chunk, lossless)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--chunkMcs", dest="chunk_mcs", type=int, default=100)
    p.add_argument("--outDir", dest="out_dir", type=str, default="escg_out")


def parse_observables(s: Optional[str]) -> Optional[Tuple[str, ...]]:
    """``--observables`` string -> tuple ('none'/'' -> (), None -> None:
    flag not given, defer to the scenario/default)."""
    if s is None:
        return None
    s = s.strip()
    if not s or s.lower() == "none":
        return ()
    return tuple(x.strip() for x in s.split(",") if x.strip())


def params_from_args(args: argparse.Namespace) -> EscgParams:
    fields = {f.name for f in dataclasses.fields(EscgParams)}
    kw = {k: v for k, v in vars(args).items() if k in fields and v is not None}
    if "tile" in kw:
        kw["tile"] = tuple(kw["tile"])
    if "observables" in kw:
        kw["observables"] = parse_observables(kw["observables"]) or ()
    if kw.get("shard_grid") is not None:
        kw["shard_grid"] = tuple(kw["shard_grid"])
    if kw.get("mesh_shape") is not None:
        kw["mesh_shape"] = tuple(kw["mesh_shape"])
    return EscgParams(**kw).validate()
