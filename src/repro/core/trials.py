"""Device-sharded IID trial subsystem — the *pod* axis (DESIGN.md §4).

The paper's replication studies hinge on massed IID trials (Park et al. ran
2000 serial repetitions for one figure; the dissertation's Table 4.2 runs 20
per cell). PR 1 decomposed one big lattice across devices (the grid axis);
this module carries the orthogonal axis: many independent lattices, one per
trial, vmapped on-device and **sharded across all local devices** over the
trial dimension. sPEGG (Okamoto & Amarasekare 2016) and the wafer-scale
agent-evolution work both show this population/trial axis is where
eco-evolutionary GPU throughput compounds.

Design invariants (tested in tests/test_trials.py):

* **Per-trial fold-in keys.** Trial ``t`` uses
  ``jax.random.fold_in(base_key, t)`` — a pure function of the base key and
  the *global* trial index, never of the trial count, the padding, or the
  device layout. Results are therefore bit-identical for any
  ``trial_devices`` and any padding, and a prefix of a larger run equals the
  smaller run (the same counter-based idiom as ``rng.tile_stream_batch`` on
  the grid axis).
* **Padding to device multiples.** ``n_trials`` is padded up to a multiple
  of the device count; padded trials run (they are indistinguishable to
  XLA's SPMD partitioner) and are dropped from every statistic on the host.
* **Chunked streaming.** ``n_mcs`` executes in jitted chunks of
  ``chunk_mcs`` (one ``lax.scan`` per chunk, fully device-resident). The
  host only ever sees per-chunk per-MCS alive-species masks — never the
  grids — and streams stasis / extinction statistics between chunks instead
  of materializing one monolithic ``(trials, mcs, ...)`` history.
* **Async stat streaming.** By default (``async_stats=True``) the driver
  keeps one chunk in flight ahead of the host: chunk k+1 is dispatched
  before chunk k's masks are pulled to the host, so stasis/extinction
  accounting overlaps device compute (double-buffered device-to-host
  copies; JAX dispatch is asynchronous). Bit-identical to the synchronous
  schedule — the speculative chunk past an early-exit is dropped unread.
* **Chunked stasis early-exit.** Per-trial stasis (<= 1 species alive,
  paper §3.2.2) is recorded at exact per-MCS resolution from the streamed
  masks, but the driver only *stops* at chunk granularity, and only once
  EVERY live trial has entered stasis (a vmapped batch advances in
  lock-step; finished trials are monocultures whose survival mask can no
  longer change, so running them to the barrier is harmless).
"""
from __future__ import annotations

import json
from dataclasses import dataclass, field
from functools import partial
from typing import Callable, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from . import engines, lattice, metrics, tracing
from . import observables as obs_mod
from .params import EscgParams
from .results import decode_observables, encode_observables

POD_AXIS = "pod"   # mesh axis name for the trial dimension


# ------------------------------ TrialResult ------------------------------- #

@dataclass
class TrialResult:
    """Streamed statistics of a batch of IID trials.

    Grids are intentionally absent: at pod scale (thousands of trials) the
    lattices stay device-resident and only the statistics below ever reach
    the host.

    ``observables`` (the ``RunResult`` protocol surface, core/results.py)
    maps registered observable names to per-trial streams flushed from
    the device ring buffer, shape ``(n_trials, T, ...)`` with T the rows
    the ring retained (== MCS consumed when the capacity covers every
    chunk; lossy wraparound drops the oldest rows per chunk otherwise).
    Empty when ``params.observables`` was empty. Note
    ``observables['densities']`` is the per-MCS density *stream*; the
    ``densities`` field keeps its legacy meaning of final densities.
    """
    survival: np.ndarray       # (n_trials, S) bool — species alive at end
    densities: np.ndarray      # (n_trials, S + 1) — final densities, col 0
                               # = empties
    stasis_mcs: np.ndarray     # (n_trials,) int — first MCS with <= 1
                               # species alive; -1 if never
    extinction_mcs: np.ndarray  # (n_trials, S) int — first MCS each species
                               # hit zero population; 0 = absent at init,
                               # -1 = never went extinct
    mcs_completed: int         # MCS every trial actually ran
    kept_fraction: float       # applied / attempted proposals (E2 audit)
    n_trials: int
    n_devices: int             # devices the batch ran on: the pod width
                               # for vmapped engines, the full composed
                               # ('pod','rows','cols') mesh size for
                               # pod-composable engines (DESIGN.md §6)
    observables: dict = field(default_factory=dict)

    # --------------------------- statistics ---------------------------- #
    @property
    def species(self) -> int:
        return self.survival.shape[1]

    def survival_probabilities(self) -> np.ndarray:
        """Per-species survival probability, shape (S,) — Park Figs 4.9+."""
        return self.survival.mean(axis=0)

    def survivors_hist(self) -> np.ndarray:
        """Histogram over the number of surviving species, shape (S + 1,),
        normalized to sum to 1 (Park n-survivor statistics)."""
        s = self.species
        return (np.bincount(self.survival.sum(axis=1).astype(np.int64),
                            minlength=s + 1)[:s + 1] / self.n_trials)

    def extinction_probability(self, sp: int) -> float:
        """P(species ``sp``, 1-indexed, extinct at end) over trials."""
        return float(1.0 - self.survival[:, sp - 1].mean())

    def mean_densities(self) -> np.ndarray:
        return self.densities.mean(axis=0)

    # ------------------------------ io --------------------------------- #
    def to_json(self) -> str:
        return json.dumps({
            "survival": self.survival.astype(int).tolist(),
            "densities": self.densities.tolist(),
            "stasis_mcs": self.stasis_mcs.tolist(),
            "extinction_mcs": self.extinction_mcs.tolist(),
            "mcs_completed": self.mcs_completed,
            "kept_fraction": self.kept_fraction,
            "n_trials": self.n_trials,
            "n_devices": self.n_devices,
            "observables": encode_observables(self.observables),
        })

    @staticmethod
    def from_json(s: str) -> "TrialResult":
        d = json.loads(s)
        return TrialResult(
            survival=np.asarray(d["survival"], dtype=bool),
            densities=np.asarray(d["densities"], dtype=np.float64),
            stasis_mcs=np.asarray(d["stasis_mcs"], dtype=np.int64),
            extinction_mcs=np.asarray(d["extinction_mcs"], dtype=np.int64),
            mcs_completed=int(d["mcs_completed"]),
            kept_fraction=float(d["kept_fraction"]),
            n_trials=int(d["n_trials"]),
            n_devices=int(d["n_devices"]),
            observables=decode_observables(d.get("observables", {})),
        )


# --------------------------- pod-axis sharding ----------------------------- #

def pod_sharding(trial_devices: Optional[int] = None) -> NamedSharding:
    """Batch sharding over the leading (trial) axis on a 1-D ``pod`` mesh
    of the first ``trial_devices`` local devices (all of them when None)."""
    devs = jax.local_devices()
    d = len(devs) if trial_devices is None else int(trial_devices)
    if d < 1:
        raise ValueError("trial_devices must be >= 1")
    if d > len(devs):
        raise ValueError(f"trial_devices={d} but only {len(devs)} local "
                         "devices are available")
    mesh = Mesh(np.asarray(devs[:d]), (POD_AXIS,))
    return NamedSharding(mesh, P(POD_AXIS))


def pad_trials(n_trials: int, n_devices: int) -> int:
    """Smallest multiple of ``n_devices`` that is >= ``n_trials`` (XLA SPMD
    needs the batch axis to divide evenly across the pod mesh)."""
    return -(-n_trials // n_devices) * n_devices


def fold_trial_keys(key: jax.Array, n: int, start: int = 0) -> jax.Array:
    """Per-trial run keys ``fold_in(key, t)`` for global trial indices
    ``start .. start + n - 1`` (see module docstring: the key is a pure
    function of the base key and the GLOBAL trial index, never of the
    batch composition — a prefix of a larger run equals the smaller run,
    and the serving layer packs many requests' key blocks into one batch
    without perturbing any trajectory)."""
    return jax.vmap(lambda i: jax.random.fold_in(key, i))(
        jnp.arange(start, start + n, dtype=jnp.int32))


def make_trial_init(p: EscgParams,
                    sharding: Optional[NamedSharding] = None,
                    grid_sharding: Optional[NamedSharding] = None):
    """``init(trial_keys) -> (grids, keys)``: initial lattices + run keys
    from per-trial fold-in keys, reusable across calls.

    The returned closure jits the per-trial ``init_one`` ONCE, so a
    long-lived caller (the serving layer's compiled-engine cache) pays
    the init trace a single time per cached engine; ``run_trials``
    routes through the same closure, keeping the two paths bit-identical
    by construction. Placement matches the driver: ``sharding`` places
    the keys BEFORE init (grids are born distributed over the trial
    axis), ``grid_sharding`` optionally reshards the grids afterwards
    (the composed path adds the ('rows','cols') lattice axes)."""
    cell_dt = jnp.dtype(p.cell_dtype)

    @jax.jit
    def init_one(tk):
        kg, kr = jax.random.split(tk)
        g = lattice.init_grid(kg, p.height, p.length, p.species, p.empty,
                              dtype=cell_dt)
        return g, kr

    def init(trial_keys):
        if sharding is not None:
            trial_keys = jax.device_put(trial_keys, sharding)
        grids, keys = jax.vmap(init_one)(trial_keys)
        if grid_sharding is not None:
            grids = jax.device_put(grids, grid_sharding)
        return grids, keys

    return init


def trial_grids_and_keys(p: EscgParams, key: jax.Array, n_pad: int,
                         sharding: Optional[NamedSharding] = None,
                         grid_sharding: Optional[NamedSharding] = None):
    """Initial lattices + per-trial run keys for ``n_pad`` trials.

    Trial ``t``'s key is ``fold_in(key, t)`` (see module docstring); the
    lattice honours ``params.cell_dtype`` exactly like ``simulate`` does
    (the legacy vmap runner silently initialized int32 grids regardless).

    ``sharding`` places the per-trial keys BEFORE init, so grids are born
    distributed over the trial axis (never materialized on one device).
    ``grid_sharding`` optionally resharding the grids afterwards — the
    composed path (§6) uses it to add the ('rows','cols') lattice axes.
    """
    trial_keys = fold_trial_keys(key, n_pad)
    return make_trial_init(p, sharding, grid_sharding)(trial_keys)


# ----------------------------- chunked driver ------------------------------ #

def build_trial_chunk(p: EscgParams, dom: jax.Array,
                      one_mcs: Optional[Callable] = None,
                      built: Optional[engines.BuiltEngine] = None,
                      pipe: Optional[obs_mod.ObsPipeline] = None):
    """chunk(grids, keys, n_mcs<static>) -> (grids, keys, final_counts,
    alive[n, n_mcs, S], kept[n], attempts[n]); jitted, device-resident.
    ``alive`` is the only per-MCS output and is what the host streams
    statistics from.

    An engine with ``EngineCaps.physics_operand`` also takes the chunk's
    keyword operand ``point`` (``engines.Physics``, each field with a
    leading trial axis): each trial's own physics, as ``run_trials``
    stacks a sweep. Without it every trial runs the point the engine was
    built with.

    Two shapes of engine fit this contract (DESIGN.md §4/§6):

    * vmappable engines: ``one_mcs(grid, key)`` is vmapped over the
      leading trial axis, the per-trial MCS loop is a ``lax.scan``;
    * pod-composable engines (``built.one_mcs_batch`` non-None): the scan
      runs at the batch level and each step advances the whole batch on
      the composed ('pod','rows','cols') mesh.

    Both thread per-trial keys identically (split once per MCS per
    trial), so they are bit-identical for any engine pair whose one-MCS
    functions are.

    With ``pipe`` (an :class:`~.observables.ObsPipeline`) each chunk
    additionally returns the banked per-MCS observable rows, shape
    ``(n_mcs, n, obs_width)`` — the device-side stream
    :func:`build_trial_obs_chunk` copies into the ring buffer. The key
    chain and every other output are bit-identical to ``pipe=None``
    (observables never consume PRNG state). Under ``k_mcs > 1``
    grid-derived slices are lag-held at launch-group boundaries exactly
    as in ``simulation.build_obs_chunk_fn``.
    """
    s = p.species
    if built is not None and built.one_mcs_batch is not None:
        if p.k_mcs > 1:
            multi_batch = built.multi_mcs_batch
            assert multi_batch is not None, \
                f"engine {p.engine!r} validated k_mcs>1 but built no " \
                "multi_mcs_batch"
            k_group = p.k_mcs

            @partial(jax.jit, static_argnames=("n_mcs",))
            def chunk_batch(grids, keys, n_mcs: int):
                n = grids.shape[0]
                q, r = divmod(n_mcs, k_group)
                kept = att = jnp.zeros((n,), jnp.int32)
                parts, row_parts = [], []
                held = (jax.vmap(pipe.grid_values)(grids)
                        if pipe is not None else None)

                def launch_rows(cnts_l, held):
                    # (n, K, S+1) -> (K, n, obs_width), lag-held grid slices
                    return jax.vmap(lambda c: jax.vmap(pipe.row_held)(
                        c, held))(jnp.moveaxis(cnts_l, 1, 0))

                if q:
                    def body(carry, _):
                        g, k, kept, att, held = carry
                        g, k, cnts, k2, a2 = multi_batch(g, k, k_group)
                        rows = (launch_rows(cnts, held)
                                if pipe is not None else jnp.int32(0))
                        if pipe is not None:
                            held = jax.vmap(pipe.grid_values)(g)
                        return (g, k, kept + k2, att + a2, held), (cnts,
                                                                   rows)
                    (grids, keys, kept, att, held), (cnts_q, rows_q) = \
                        jax.lax.scan(body, (grids, keys, kept, att, held),
                                     length=q)
                    # (q, n, K, S + 1) -> (n, q * K, S + 1)
                    parts.append(jnp.moveaxis(cnts_q, 0, 1).reshape(
                        n, q * k_group, s + 1))
                    if pipe is not None:
                        # (q, K, n, W) -> (q * K, n, W)
                        row_parts.append(rows_q.reshape(
                            q * k_group, n, pipe.width))
                if r:
                    grids, keys, cnts_r, k2, a2 = multi_batch(grids, keys,
                                                              r)
                    kept, att = kept + k2, att + a2
                    parts.append(cnts_r)
                    if pipe is not None:
                        row_parts.append(launch_rows(cnts_r, held))
                cnts = jnp.concatenate(parts, axis=1)
                out = (grids, keys, cnts[:, -1], cnts[:, :, 1:] > 0,
                       kept, att)
                if pipe is not None:
                    out += (jnp.concatenate(row_parts, axis=0),)
                return out

            return chunk_batch

        one_mcs_batch = built.one_mcs_batch

        @partial(jax.jit, static_argnames=("n_mcs",))
        def chunk_batch(grids, keys, n_mcs: int):
            zeros = jnp.zeros((grids.shape[0],), jnp.int32)

            def body(carry, _):
                g, k, kept, att = carry
                both = jax.vmap(jax.random.split)(k)
                k, k1 = both[:, 0], both[:, 1]
                g, k2, a2 = one_mcs_batch(g, k1)
                cnts = jax.vmap(lambda x: metrics.counts(x, s))(g)
                rows = (jax.vmap(pipe.row)(g, cnts)
                        if pipe is not None else jnp.int32(0))
                return (g, k, kept + k2, att + a2), (cnts, rows)
            (g, k, kept, att), (cnts, rows) = jax.lax.scan(
                body, (grids, keys, zeros, zeros), length=n_mcs)
            cnts = jnp.moveaxis(cnts, 0, 1)      # (n, n_mcs, S + 1)
            out = (g, k, cnts[:, -1], cnts[:, :, 1:] > 0, kept, att)
            if pipe is not None:
                out += (rows,)                   # (n_mcs, n, W)
            return out

        return chunk_batch

    if one_mcs is None and (built is None and p.k_mcs > 1):
        built = engines.build(p, dom)
    if one_mcs is None:
        one_mcs = (built.one_mcs if built is not None
                   else engines.build(p, dom).one_mcs)
    multi = (built.multi_mcs
             if built is not None and p.k_mcs > 1 else None)

    if p.k_mcs > 1:
        assert multi is not None, \
            f"engine {p.engine!r} validated k_mcs>1 but built no multi_mcs"
        k_group = p.k_mcs

        @partial(jax.jit, static_argnames=("n_mcs",))
        def chunk(grids, keys, n_mcs: int):
            def one(grid, key):
                q, r = divmod(n_mcs, k_group)
                kept = att = jnp.int32(0)
                parts, row_parts = [], []
                held = (pipe.grid_values(grid) if pipe is not None
                        else None)
                if q:
                    def body(carry, _):
                        g, k, kept, att, held = carry
                        g, k, cnts, k2, a2 = multi(g, k, k_group)
                        rows = (jax.vmap(lambda c: pipe.row_held(c, held))(
                            cnts) if pipe is not None else jnp.int32(0))
                        if pipe is not None:
                            held = pipe.grid_values(g)
                        return (g, k, kept + k2, att + a2, held), (cnts,
                                                                   rows)
                    (grid, key, kept, att, held), (cnts_q, rows_q) = \
                        jax.lax.scan(body, (grid, key, kept, att, held),
                                     length=q)
                    parts.append(cnts_q.reshape(q * k_group, s + 1))
                    if pipe is not None:
                        row_parts.append(rows_q.reshape(q * k_group,
                                                        pipe.width))
                if r:
                    grid, key, cnts_r, k2, a2 = multi(grid, key, r)
                    kept, att = kept + k2, att + a2
                    parts.append(cnts_r)
                    if pipe is not None:
                        row_parts.append(jax.vmap(
                            lambda c: pipe.row_held(c, held))(cnts_r))
                cnts = jnp.concatenate(parts, axis=0)
                out = (grid, key, cnts[-1], cnts[:, 1:] > 0, kept, att)
                if pipe is not None:
                    out += (jnp.concatenate(row_parts, axis=0),)
                return out
            out = jax.vmap(one)(grids, keys)
            if pipe is not None:
                # per-trial (n, n_mcs, W) -> ring layout (n_mcs, n, W)
                out = out[:6] + (jnp.moveaxis(out[6], 0, 1),)
            return out

        return chunk

    @partial(jax.jit, static_argnames=("n_mcs",))
    def chunk(grids, keys, n_mcs: int, point=None):
        def one(grid, key, point):
            step = engines.stepper(one_mcs, point)

            def body(carry, _):
                g, k, kept, att = carry
                k, k1 = jax.random.split(k)
                g, k2, a2 = step(g, k1)
                cnt = metrics.counts(g, s)
                row = (pipe.row(g, cnt) if pipe is not None
                       else jnp.int32(0))
                return (g, k, kept + k2, att + a2), (cnt, row)
            (g, k, kept, att), (cnts, rows) = jax.lax.scan(
                body, (grid, key, jnp.int32(0), jnp.int32(0)), length=n_mcs)
            out = (g, k, cnts[-1], cnts[:, 1:] > 0, kept, att)
            if pipe is not None:
                out += (rows,)
            return out
        out = jax.vmap(one)(grids, keys, point)   # a sweep's point per trial
        if pipe is not None:
            out = out[:6] + (jnp.moveaxis(out[6], 0, 1),)
        return out

    return chunk


def build_trial_obs_chunk(p: EscgParams, dom: jax.Array,
                          built: Optional[engines.BuiltEngine] = None):
    """Observable-pipeline trial chunk (DESIGN.md §11): ``chunk(grids,
    keys, ring, pos, n_mcs<static>, **operands) -> (grids, keys, ring,
    pos, final_counts, alive, kept, attempts)``, the operands (``point``)
    as in :func:`build_trial_chunk`; returns ``(chunk, pipeline)``.

    The banked per-MCS rows are copied into the device-resident ring
    buffer (shape ``(capacity, n_pad, obs_width)``) inside the jitted
    chunk — the host never sees a per-MCS transfer; ``run_trials``
    flushes the ring once per *consumed* chunk on the same speculative
    double-buffered stream as the alive-masks. Capacity below the chunk
    length drops the oldest rows (documented lossy wraparound; the
    stasis/extinction statistics stream from ``alive``, not the ring).
    """
    pipe = obs_mod.build_pipeline(p)
    inner = build_trial_chunk(p, dom, built=built, pipe=pipe)

    @partial(jax.jit, static_argnames=("n_mcs",))
    def chunk(grids, keys, ring, pos, n_mcs: int, **operands):
        grids, keys, cnts, alive, kept, att, rows = inner(
            grids, keys, n_mcs=n_mcs, **operands)
        ring, pos = obs_mod.ring_push_many(ring, pos, rows)
        return grids, keys, ring, pos, cnts, alive, kept, att

    return chunk, pipe


def _first_true_mcs(mask: np.ndarray, offset: int) -> np.ndarray:
    """First 1-based MCS index of a True along axis 1 of ``mask``
    (trials-leading), offset by the MCS already completed; -1 where the
    event never happens in this chunk. Works on any trailing shape."""
    hit = mask.any(axis=1)
    first = mask.argmax(axis=1) + offset + 1
    return np.where(hit, first, -1)


def run_trials(params: Union[EscgParams, Sequence[EscgParams]],
               dom: Optional[np.ndarray] = None,
               n_trials: int = 1, key: Optional[jax.Array] = None,
               n_mcs: Optional[int] = None,
               trial_devices: Optional[int] = None,
               chunk_mcs: Optional[int] = None,
               stop_on_stasis: bool = True,
               hooks: Sequence[Callable[[int, np.ndarray], None]] = (),
               async_stats: bool = True,
               engine_config=None, run_config=None, *,
               engine=None, run=None,
               ) -> Union[TrialResult, List[TrialResult]]:
    """Run ``n_trials`` IID simulations, vmapped and device-sharded.

    Scenario-first signature (DESIGN.md §10): ``run_trials(scenario,
    n_trials=..., engine=EngineConfig(...), run=RunConfig(...))`` — the
    primary positional argument is a ``Scenario``; ``dom=None`` derives
    the dominance network from the scenario registry, and the scenario's
    declared observables stream through the device ring buffer
    (DESIGN.md §11) unless ``run.observables`` pins the set. The legacy
    flat form ``run_trials(params, dom, ...)`` still works behind a
    ``DeprecationWarning`` (``engine_config=``/``run_config=`` are the
    equally-deprecated spellings of ``engine=``/``run=``).

    A sweep (DESIGN.md §4): given a list of scenarios in place of one,
    the call runs ``n_trials`` of each as ONE batch,
    point-major, with one chunk program, and returns a list with one
    ``TrialResult`` per scenario, in order. The scenarios must share
    every field that sets the batch's shape (``scenarios.resolve_points``
    raises a ``ValueError`` naming the field that differs); each trial
    gets its point's dominance matrix and action thresholds as operands
    of the engine, so the engine must take them
    (``EngineCaps.physics_operand``: sublattice, reference, batched).
    Trial i of every point uses the key ``fold_in(key, i)``, so point p's
    result is bit-identical to ``run_trials(scenario_p, n_trials=...)``
    with the same key: common random numbers across the points. Hooks get
    the alive counts of all P × n_trials trials, point-major, and the
    stasis early exit waits for every trial of every point.

    The batch is padded to a multiple of the pod width (``trial_devices``,
    default: all local devices), placed with the trial axis sharded across
    the pod mesh, and advanced in jitted chunks of ``chunk_mcs`` MCS
    (default ``params.chunk_mcs``). Between chunks the host streams
    alive-species masks into per-trial stasis / extinction statistics and —
    when ``stop_on_stasis`` — exits early once every trial has reached
    stasis (see module docstring for the exact chunked semantics).

    Pod-composable engines (``EngineCaps.mesh_axes`` containing 'pod',
    e.g. ``engine='sharded_pod'``) run the same pipeline on a composed
    ``('pod', 'rows', 'cols')`` mesh: trials shard over the pod axis while
    every trial's lattice is additionally domain-decomposed with halo
    exchange (DESIGN.md §6). The device layout comes from
    ``params.mesh_shape`` (``trial_devices`` must stay None) and the batch
    pads to the pod width only. Results are bit-identical to the vmapped
    single-device path for any mesh factorization.

    ``hooks`` fire after every chunk with ``(mcs_done, alive_counts)``
    where ``alive_counts`` is the (n_trials,) number of species alive per
    trial at the chunk boundary.

    ``async_stats`` (default True) streams the per-chunk statistics OFF
    the critical path: chunk k+1 is dispatched (JAX dispatch is
    asynchronous) *before* the host touches chunk k's alive-masks, so the
    stasis/extinction accounting overlaps the next chunk's device compute
    instead of serializing on it (double-buffered device-to-host copies).
    Results are bit-identical either way — the host consumes exactly the
    same arrays in the same order; the one speculative chunk in flight
    past a stasis early-exit is discarded unconsumed, so ``mcs_completed``
    and every statistic match the synchronous schedule exactly.

    Bit-identical for any ``trial_devices`` and any padding: per-trial
    PRNG keys are ``fold_in(key, trial_index)``.

    With ``params.observables`` non-empty the per-MCS observable rows of
    every (padded) trial are banked into a device ring buffer inside each
    chunk and flushed once per CONSUMED chunk — the speculative in-flight
    chunk dropped by a stasis early-exit is never flushed, so the
    observable streams are flush-schedule invariant (identical for
    ``async_stats`` True/False and any chunk length, capacity
    permitting).

    Each chunk boundary runs in the ``escg.*`` spans of ``core/tracing.py``
    and is kept, with its count of blocking reads, in
    ``tracing.last_run()``, beside the call's ``traces`` of the chunk
    program and its ``points``.
    """
    from .scenarios import resolve_points  # lazy: scenarios imports core
    from .simulation import _resolve_call_form  # lazy: avoid cycle
    loop = tracing.begin("run_trials")
    engine_config, run_config = _resolve_call_form(
        "run_trials", params, engine_config, run_config, engine, run)
    sweep = isinstance(params, (list, tuple))
    p, points = resolve_points(params if sweep else [params], dom,
                               engine_config, run_config)
    n_points = loop.totals["points"] = len(points)
    spec = engines.get_engine(p.engine)
    composed = spec.caps.pod_composable
    if composed:
        if trial_devices is not None:
            raise ValueError(
                f"engine {p.engine!r} lays devices on a composed "
                "('pod','rows','cols') mesh — set the pod width through "
                "params.mesh_shape, not trial_devices")
    elif not spec.caps.vmappable:
        raise ValueError(
            f"engine {p.engine!r} is not vmappable (multi-device engines "
            "decompose one lattice); run IID trials with a single-device "
            "engine and shard the trial axis, or compose the two axes "
            "with engine='sharded_pod' (mesh_shape=(pod, rows, cols))")
    if not composed and not spec.caps.trial_shardable \
            and (trial_devices or 1) > 1:
        raise ValueError(f"engine {p.engine!r} does not support trial-axis "
                         "sharding; use trial_devices=1")
    if n_points > 1 and not spec.caps.physics_operand:
        raise ValueError(
            f"engine {p.engine!r} compiles its rates into its program "
            "(EngineCaps.physics_operand is False), so a batch holds one "
            f"physics point, not {n_points}; sweep on "
            f"{_operand_engines()} or run one point per call")
    if n_trials < 1:
        raise ValueError("n_trials must be >= 1")
    dom_j = points[0].dom
    if key is None:
        key = jax.random.PRNGKey(p.seed)
    n_mcs = int(n_mcs if n_mcs is not None else p.mcs)
    if chunk_mcs is not None and chunk_mcs < 1:
        raise ValueError("chunk_mcs must be >= 1")
    # n_mcs == 0 is legal: the loop below never runs and the result carries
    # the initial survival mask / densities (legacy vmap-runner behaviour)
    chunk_len = int(chunk_mcs if chunk_mcs is not None
                    else max(1, min(p.chunk_mcs, n_mcs)))

    if composed:
        # composed pod x grid mesh (DESIGN.md §6): the engine owns the
        # device layout; the driver only pads the batch to the pod width
        # and places arrays on the engine's shardings.
        built = engines.build(p, dom_j)
        n_dev = built.batch_sharding.mesh.devices.size
        n_pad = pad_trials(n_trials, built.pod_width)
        # keys are placed pod-sharded BEFORE init, so every trial's grid
        # is born on its pod group; the reshard then only splits each
        # lattice over its group's ('rows','cols') axes — the full batch
        # never materializes on a single device
        grids, keys = trial_grids_and_keys(
            p, key, n_pad, sharding=built.key_sharding,
            grid_sharding=built.batch_sharding)
        pod_mesh = built.key_sharding.mesh
        if p.observables:
            chunk_fn, pipe = build_trial_obs_chunk(p, dom_j, built=built)
        else:
            chunk_fn = build_trial_chunk(p, dom_j, built=built)
    else:
        sharding = (pod_sharding(trial_devices) if spec.caps.trial_shardable
                    else pod_sharding(1))
        n_dev = sharding.mesh.devices.size
        n_pad = pad_trials(n_trials, n_dev)
        # point-major: every point's block of n_pad trials has the keys,
        # and so the initial lattices, of a single-point call
        trial_keys = fold_trial_keys(key, n_pad)
        grids, keys = make_trial_init(p, sharding)(
            jnp.concatenate([trial_keys] * n_points))
        pod_mesh = sharding.mesh
        if p.observables:
            chunk_fn, pipe = build_trial_obs_chunk(p, dom_j)
        else:
            chunk_fn = build_trial_chunk(p, dom_j)
    chunk_fn = loop.counted(chunk_fn)
    operands = {}
    if n_points > 1:
        # a sweep hands each trial its point's physics; one point is the
        # engine's own, built into the program as a single call's is
        operands["point"] = jax.device_put(jax.tree.map(
            lambda *xs: jnp.repeat(jnp.stack(xs), n_pad, axis=0), *points),
            sharding)
    n_batch = n_points * n_pad

    def per_point(a):
        """(n_batch, ...) -> (n_points, n_trials, ...): padding dropped."""
        return a.reshape((n_points, n_pad) + a.shape[1:])[:, :n_trials]

    obs_on = bool(p.observables)
    ring = pos = None
    rows_all = []
    if obs_on:
        cap = obs_mod.ring_capacity(p, max(1, chunk_len))
        ring, pos = obs_mod.ring_init(cap, (n_batch, pipe.width))
        # ring rows shard with the trial axis — flushes stay device-local
        # per pod group until the host copy
        ring = jax.device_put(
            ring, NamedSharding(pod_mesh, P(None, POD_AXIS)))

    s = p.species
    # species absent at initialization count as extinct at MCS 0
    init_cnts = np.asarray(jax.jit(jax.vmap(
        lambda g: metrics.counts(g, s)))(grids))
    ext = np.where(init_cnts[:, 1:] > 0, -1, 0).astype(np.int64)
    stasis = np.full(n_batch, -1, np.int64)
    surv = init_cnts[:, 1:] > 0
    final_cnts = init_cnts
    kept_tot = att_tot = np.zeros(n_points, np.int64)
    done = 0

    # One chunk is kept in flight ahead of the host (async_stats): the
    # wait below blocks on the chunk being *consumed* while the
    # speculatively dispatched successor already computes. On a stasis
    # early-exit the in-flight chunk is simply dropped — its outputs are
    # never read, so statistics and mcs_completed are schedule-independent.
    def dispatch(grids, keys, ring, pos, m):
        if obs_on:
            return chunk_fn(grids, keys, ring, pos, n_mcs=m, **operands)
        g, k, cnts, alive, kept, att = chunk_fn(grids, keys, n_mcs=m,
                                                **operands)
        return g, k, None, None, cnts, alive, kept, att

    m = min(chunk_len, n_mcs)
    out = None
    if n_mcs:
        with loop.span(tracing.DISPATCH):
            out = dispatch(grids, keys, ring, pos, m)
    while out is not None:
        grids, keys, ring, pos, cnts, alive, kept, att = out
        m_next = min(chunk_len, n_mcs - done - m)
        out = None
        if m_next and async_stats:
            with loop.span(tracing.DISPATCH):
                out = dispatch(grids, keys, ring, pos, m_next)

        # the ring is already an input of the successor; it is an output
        # of the same execution as these, so it is ready when they are
        with loop.span(tracing.WAIT):
            jax.block_until_ready((alive, cnts, kept, att))
        with loop.span(tracing.READBACK):
            alive_h = loop.read(alive)                # (n_batch, m, S) bool
            ring_h = loop.read(ring) if obs_on else None
            final_cnts = loop.read(cnts)
            kept_h, att_h = loop.read(kept), loop.read(att)
        with loop.span(tracing.HOST_STATS):
            if obs_on:
                # one flush per CONSUMED chunk (the in-flight speculative
                # chunk past an early-exit is dropped unflushed)
                rows_all.append(obs_mod.ring_flush(ring_h, done, done + m))
            kept_tot = kept_tot + per_point(kept_h).sum(axis=1)
            att_tot = att_tot + per_point(att_h).sum(axis=1)

            first_dead = _first_true_mcs(~alive_h, done)     # (n_batch, S)
            ext = np.where((ext < 0) & (first_dead > 0), first_dead, ext)
            first_stasis = _first_true_mcs(alive_h.sum(axis=2) <= 1, done)
            stasis = np.where((stasis < 0) & (first_stasis > 0),
                              first_stasis, stasis)
            surv = alive_h[:, -1, :]
            done += m
        with loop.span(tracing.HOOKS):
            for hook in hooks:
                hook(done, per_point(surv).sum(axis=2).reshape(-1))
        loop.close(done)
        if stop_on_stasis and (per_point(stasis) >= 0).all():
            break
        if m_next and out is None:                   # async_stats=False
            with loop.span(tracing.DISPATCH):
                out = dispatch(grids, keys, ring, pos, m_next)
        m = m_next

    streams = [{} for _ in range(n_points)]
    if obs_on and rows_all:
        rows = np.concatenate(rows_all, axis=0)      # (T, n_batch, W)
        streams = [pipe.split(r) for r in per_point(np.moveaxis(rows, 0, 1))]

    results = [TrialResult(
        survival=per_point(surv)[q].astype(bool),
        densities=per_point(final_cnts)[q] / p.n_cells,
        stasis_mcs=per_point(stasis)[q],
        extinction_mcs=per_point(ext)[q],
        mcs_completed=done,
        kept_fraction=float(kept_tot[q] / att_tot[q]) if att_tot[q] else 1.0,
        n_trials=n_trials,
        n_devices=n_dev,
        observables=streams[q],
    ) for q in range(n_points)]
    return results if sweep else results[0]


def _operand_engines() -> str:
    return ", ".join(s.name for s in engines.engine_specs()
                     if s.caps.physics_operand)
