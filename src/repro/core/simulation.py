"""MCS driver (paper Algorithms 3.3 / 3.5 / 3.6 / 3.7, unified).

The paper's lesson (maxStep, §4.2.4): keep everything device-resident and
batch many Monte-Carlo steps per launch. Here a *chunk* of ``chunk_mcs`` MCS
runs inside one jitted ``lax.scan``; the host only sees per-MCS population
counts, performs the stasis early-exit (paper §3.2.2), and fires snapshot /
checkpoint hooks between chunks.

Engine selection is delegated entirely to the registry in ``engines.py``;
this module never branches on the engine name. For multi-device engines the
registry hands back a grid sharding: the lattice is placed once and the
per-MCS population counts (a ``bincount`` over the sharded lattice) lower
to per-shard partial counts plus an all-reduce, so the stasis early-exit
sees global populations without ever gathering the grid to one device.
"""
from __future__ import annotations

import json
import warnings
from dataclasses import dataclass, field
from functools import partial
from typing import Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import dominance as dom_mod
from . import engines, lattice, metrics, tracing
from . import observables as obs_mod
from .params import EscgParams
from .results import decode_observables, encode_observables

_SCENARIO_FIRST_MSG = (
    "the flat-facade call form ({fn}(params, dom, ...)) is deprecated; "
    "pass a Scenario first — {fn}(scenario, engine=EngineConfig(...), "
    "run=RunConfig(...)) — and let the registry resolve the dominance "
    "network (DESIGN.md §10/§11)")


def _resolve_call_form(fn_name, params, engine_config, run_config,
                       engine, run):
    """Scenario-first signature shim shared by ``simulate`` and
    ``trials.run_trials``: ``engine=``/``run=`` are the preferred
    spellings of ``engine_config=``/``run_config=`` (error if both are
    given), and a flat ``EscgParams`` in the scenario slot warns."""
    if engine is not None:
        if engine_config is not None:
            raise TypeError(f"{fn_name}: pass engine= or engine_config=, "
                            "not both")
        engine_config = engine
    if run is not None:
        if run_config is not None:
            raise TypeError(f"{fn_name}: pass run= or run_config=, "
                            "not both")
        run_config = run
    if isinstance(params, EscgParams):
        warnings.warn(_SCENARIO_FIRST_MSG.format(fn=fn_name),
                      DeprecationWarning, stacklevel=3)
    return engine_config, run_config


@dataclass
class SimResult:
    """Single-lattice run result (one half of the ``RunResult`` protocol,
    core/results.py; ``trials.TrialResult`` is the other).

    ``observables`` maps registered observable names to their flushed
    per-MCS streams. ``densities`` always present: shape
    ``(mcs_recorded + 1, S + 1)`` float64 with row 0 the initial lattice
    — exactly the legacy field, whether or not the device observable
    pipeline ran. Other streams (``interface_length``, ``snapshot``, ...)
    have ``post``-finalized shape ``(mcs_recorded, ...)`` with no initial
    row, appearing only when ``params.observables`` requested them.
    """
    grid: np.ndarray               # final lattice (H, W)
    observables: Dict[str, np.ndarray] = field(default_factory=dict)
    mcs_completed: int = 0
    stasis_mcs: int = -1           # -1 if never reached stasis
    kept_fraction: float = 1.0     # applied / attempted proposals (E2 audit)

    @property
    def densities(self) -> np.ndarray:
        """Deprecated alias for ``observables['densities']`` (kept for
        figure modules and goldens; prefer the observables mapping)."""
        return self.observables["densities"]

    def to_json(self) -> str:
        return json.dumps({
            "grid": np.asarray(self.grid).tolist(),
            "grid_dtype": str(np.asarray(self.grid).dtype),
            "observables": encode_observables(self.observables),
            "mcs_completed": int(self.mcs_completed),
            "stasis_mcs": int(self.stasis_mcs),
            "kept_fraction": float(self.kept_fraction),
        })

    @staticmethod
    def from_json(s: str) -> "SimResult":
        d = json.loads(s)
        return SimResult(
            grid=np.asarray(d["grid"], dtype=np.dtype(d["grid_dtype"])),
            observables=decode_observables(d["observables"]),
            mcs_completed=d["mcs_completed"],
            stasis_mcs=d["stasis_mcs"],
            kept_fraction=d["kept_fraction"])


def build_mcs_fn(params: EscgParams, dom: jax.Array):
    """one_mcs(grid, key) -> (grid, kept, attempts), resolved via the
    engine registry (back-compat shim; prefer engines.build for access to
    the grid sharding)."""
    return engines.build(params, dom).one_mcs


def build_chunk_fn(params: EscgParams, dom: jax.Array,
                   one_mcs: Optional[Callable] = None, built=None):
    """chunk(grid, key, n_mcs<static>) -> (grid, key, counts[n,S+1], kept,
    attempts); jit-compiled, fully device-resident.

    With ``params.k_mcs > 1`` (and a ``built`` engine providing
    ``multi_mcs``) the chunk runs in K-step megakernel groups — a scan of
    ``n_mcs // K`` multi-MCS launches plus one remainder launch — instead
    of one launch per MCS. Counts, key chain and trajectory are
    bit-identical to the per-MCS path (the k_mcs contract)."""
    if built is None and (one_mcs is None or params.k_mcs > 1):
        built = engines.build(params, dom)
    if one_mcs is None:
        one_mcs = built.one_mcs
    s = params.species

    if params.k_mcs > 1:
        multi = built.multi_mcs
        assert multi is not None, \
            f"engine {params.engine!r} validated k_mcs>1 but built no " \
            "multi_mcs"
        k_group = params.k_mcs

        @partial(jax.jit, static_argnames=("n_mcs",))
        def chunk(grid, key, n_mcs: int):
            q, r = divmod(n_mcs, k_group)
            kept, att = jnp.int32(0), jnp.int32(0)
            parts = []
            if q:
                def body(carry, _):
                    g, k, kept, att = carry
                    g, k, cnts, k2, a2 = multi(g, k, k_group)
                    return (g, k, kept + k2, att + a2), cnts
                (grid, key, kept, att), cnts_q = jax.lax.scan(
                    body, (grid, key, kept, att), length=q)
                parts.append(cnts_q.reshape(q * k_group, s + 1))
            if r:
                grid, key, cnts_r, k2, a2 = multi(grid, key, r)
                kept, att = kept + k2, att + a2
                parts.append(cnts_r)
            cnts = (jnp.concatenate(parts, axis=0) if parts
                    else jnp.zeros((0, s + 1), jnp.int32))
            return grid, key, cnts, kept, att

        return chunk

    @partial(jax.jit, static_argnames=("n_mcs",))
    def chunk(grid, key, n_mcs: int):
        def body(carry, _):
            g, k, kept, att = carry
            k, k1 = jax.random.split(k)
            g, k2, a2 = one_mcs(g, k1)
            cnt = metrics.counts(g, s)
            return (g, k, kept + k2, att + a2), cnt
        (grid, key, kept, att), cnts = jax.lax.scan(
            body, (grid, key, jnp.int32(0), jnp.int32(0)), length=n_mcs)
        return grid, key, cnts, kept, att

    return chunk


def build_obs_chunk_fn(params: EscgParams, dom: jax.Array, built=None):
    """Observable-pipeline chunk (DESIGN.md §11): ``chunk(grid, key, ring,
    pos, n_mcs<static>) -> (grid, key, ring, pos, kept, attempts)``.

    Returns ``(chunk, pipeline)``. Unlike :func:`build_chunk_fn` the
    per-MCS species counts never leave the device as a separate output —
    every per-MCS statistic (the ``densities`` raw-count columns included)
    is banked into the ring buffer inside the jitted chunk, and the host
    reconstructs counts from the flushed rows at chunk boundaries
    (``ObsPipeline.counts_from_rows``). The engine key chain is IDENTICAL
    to the plain chunk — ``observe`` consumes no PRNG state — so
    trajectories are bit-identical with observables on or off.

    Under ``k_mcs > 1`` grid state between megakernel launches never
    leaves the kernel: count-derived slices keep per-MCS cadence from the
    banked (K, S+1) counts, grid-derived slices are lag-held at the value
    sampled at the previous launch-group boundary (module docstring of
    core/observables.py).
    """
    if built is None:
        built = engines.build(params, dom)
    pipe = obs_mod.build_pipeline(params)
    observe = built.observe or pipe.row
    s = params.species

    if params.k_mcs > 1:
        multi = built.multi_mcs
        assert multi is not None, \
            f"engine {params.engine!r} validated k_mcs>1 but built no " \
            "multi_mcs"
        k_group = params.k_mcs

        if built.grid_sharding is not None:
            # pin held values replicated across the grid mesh — same
            # check_rep=False partitioner hazard as the generic observe
            # hook wrap in engines.build (ring rows otherwise get summed
            # across a mesh axis)
            _rep = jax.sharding.NamedSharding(
                built.grid_sharding.mesh, jax.sharding.PartitionSpec())

            def grid_vals(grid):
                return {k: jax.lax.with_sharding_constraint(v, _rep)
                        for k, v in pipe.grid_values(grid).items()}
        else:
            grid_vals = pipe.grid_values

        @partial(jax.jit, static_argnames=("n_mcs",))
        def chunk(grid, key, ring, pos, n_mcs: int):
            kept, att = jnp.int32(0), jnp.int32(0)
            held = grid_vals(grid)   # lag-hold state (group boundary)

            def launch(grid, key, ring, pos, kept, att, held, k_steps):
                grid, key, cnts, k2, a2 = multi(grid, key, k_steps)
                rows = jax.vmap(lambda c: pipe.row_held(c, held))(cnts)
                ring, pos = obs_mod.ring_push_many(ring, pos, rows)
                held = grid_vals(grid)
                return grid, key, ring, pos, kept + k2, att + a2, held

            q, r = divmod(n_mcs, k_group)
            if q:
                def body(carry, _):
                    return launch(*carry, k_group), None
                (grid, key, ring, pos, kept, att, held), _ = jax.lax.scan(
                    body, (grid, key, ring, pos, kept, att, held), length=q)
            if r:
                grid, key, ring, pos, kept, att, held = launch(
                    grid, key, ring, pos, kept, att, held, r)
            return grid, key, ring, pos, kept, att

        return chunk, pipe

    one_mcs = built.one_mcs

    @partial(jax.jit, static_argnames=("n_mcs",))
    def chunk(grid, key, ring, pos, n_mcs: int):
        def body(carry, _):
            g, k, ring, pos, kept, att = carry
            k, k1 = jax.random.split(k)
            g, k2, a2 = one_mcs(g, k1)
            cnt = metrics.counts(g, s)
            ring, pos = obs_mod.ring_push(ring, pos, observe(g, cnt))
            return (g, k, ring, pos, kept + k2, att + a2), None
        (grid, key, ring, pos, kept, att), _ = jax.lax.scan(
            body, (grid, key, ring, pos, jnp.int32(0), jnp.int32(0)),
            length=n_mcs)
        return grid, key, ring, pos, kept, att

    return chunk, pipe


def simulate(params: EscgParams,
             dom: Optional[np.ndarray] = None,
             grid0: Optional[jax.Array] = None,
             key: Optional[jax.Array] = None,
             hooks: Sequence[Callable[[int, jax.Array, np.ndarray], None]] = (),
             stop_on_stasis: bool = True,
             engine_config=None, run_config=None, *,
             engine=None, run=None) -> SimResult:
    """Run the full simulation (paper Algorithm 3.3 control flow).

    Scenario-first signature: ``simulate(scenario, engine=EngineConfig(...),
    run=RunConfig(...))`` — the primary positional argument is a
    ``Scenario`` (DESIGN.md §10); ``dom=None`` derives the dominance
    network from the scenario registry, and the scenario's declared
    observables stream through the device ring buffer (DESIGN.md §11)
    unless ``run.observables`` pins the set. The legacy flat form
    ``simulate(params, dom, ...)`` still works behind a
    ``DeprecationWarning`` (``engine_config=``/``run_config=`` are the
    equally-deprecated spellings of ``engine=``/``run=``).

    Chunked stasis early-exit semantics (paper §3.2.2): each jitted chunk
    returns per-MCS population counts; the host scans them for the first
    MCS with <= 1 species alive. ``stasis_mcs`` is therefore exact to the
    MCS, but the run only *stops* at the next chunk boundary — up to
    ``chunk_mcs - 1`` extra MCS execute after stasis (their counts are
    still recorded in ``densities``). Hooks fire once per chunk, including
    the chunk in which stasis was detected. The trial-batch counterpart
    (``trials.run_trials``) applies the same rule per trial and exits only
    when every trial has reached stasis.

    With ``params.observables`` non-empty every per-MCS statistic —
    including the species counts the stasis early-exit and hooks consume —
    is banked on device into the observable ring buffer and flushed ONCE
    per chunk; there is no separate per-MCS counts transfer (the
    ``print_frequency`` density path reads the same flushed rows). The
    ring must hold a full chunk (``obs_capacity`` >= effective chunk, or
    0 = auto-size to one chunk).

    Each chunk boundary runs in the ``escg.*`` spans of ``core/tracing.py``
    and is kept, with its count of blocking reads, in
    ``tracing.last_run()``.
    """
    from .scenarios import resolve_config  # lazy: scenarios imports core
    loop = tracing.begin("simulate")
    engine_config, run_config = _resolve_call_form(
        "simulate", params, engine_config, run_config, engine, run)
    params, dom = resolve_config(params, dom, engine_config, run_config)
    p = params.validate()
    if dom is None:
        dom = dom_mod.circulant(p.species)
    dom_j = jnp.asarray(dom, jnp.float32)
    if key is None:
        key = jax.random.PRNGKey(p.seed)
    cell_dt = jnp.dtype(p.cell_dtype)
    if grid0 is None:
        key, k0 = jax.random.split(key)
        grid0 = lattice.init_grid(k0, p.height, p.length, p.species, p.empty,
                                  dtype=cell_dt)
    grid = jnp.asarray(grid0, cell_dt)

    eng = engines.build(p, dom_j)
    if eng.grid_sharding is not None:
        grid = jax.device_put(grid, eng.grid_sharding)
    n = p.n_cells
    obs_on = bool(p.observables)
    pipe, ring, pos, rows_all = None, None, None, []
    if obs_on:
        chunk_fn, pipe = build_obs_chunk_fn(p, dom_j, built=eng)
        max_chunk = max(1, min(p.chunk_mcs, p.mcs))
        cap = obs_mod.ring_capacity(p, max_chunk)
        if cap < max_chunk:
            raise ValueError(
                f"obs_capacity {cap} < chunk rows {max_chunk}: simulate "
                "flushes the ring once per chunk and its stasis accounting "
                "reads every row, so the ring must hold a full chunk "
                "(0 = auto-size)")
        ring, pos = obs_mod.ring_init(cap, (pipe.width,))
    else:
        chunk_fn = build_chunk_fn(p, dom_j, built=eng)
    chunk_fn = loop.counted(chunk_fn)
    hist = [np.asarray(metrics.counts(grid, p.species))]
    mcs_done, stasis_mcs = 0, -1
    kept_total, att_total = 0, 0

    while mcs_done < p.mcs:
        n_mcs = min(p.chunk_mcs, p.mcs - mcs_done)
        with loop.span(tracing.DISPATCH):
            if obs_on:
                grid, key, ring, pos, kept, att = chunk_fn(
                    grid, key, ring, pos, n_mcs=n_mcs)
            else:
                grid, key, cnts, kept, att = chunk_fn(
                    grid, key, n_mcs=n_mcs)
        # the flushed ring rows carry every per-MCS statistic, counts
        # included: no separate per-MCS counts transfer
        per_mcs = ring if obs_on else cnts
        with loop.span(tracing.WAIT):
            jax.block_until_ready((per_mcs, kept, att))
        with loop.span(tracing.READBACK):
            per_mcs_h = loop.read(per_mcs)
            kept_h, att_h = int(loop.read(kept)), int(loop.read(att))
        with loop.span(tracing.HOST_STATS):
            if obs_on:
                rows_h = obs_mod.ring_flush(per_mcs_h, mcs_done,
                                            mcs_done + n_mcs)
                rows_all.append(rows_h)
                cnts_h = pipe.counts_from_rows(rows_h, p.species)
            else:
                cnts_h = per_mcs_h
            hist.append(cnts_h)
            kept_total += kept_h
            att_total += att_h
            mcs_done += n_mcs
            alive = (cnts_h[:, 1:] > 0).sum(axis=1)
            if stop_on_stasis and stasis_mcs < 0 and np.any(alive <= 1):
                stasis_mcs = (mcs_done - n_mcs
                              + int(np.argmax(alive <= 1)) + 1)
        with loop.span(tracing.HOOKS):
            for hook in hooks:
                hook(mcs_done, grid, cnts_h)
        loop.close(mcs_done)
        if stop_on_stasis and stasis_mcs >= 0:
            break

    densities = np.concatenate([hist[0][None, :]] + hist[1:], axis=0) / n
    observables = {"densities": densities}
    if obs_on and rows_all:
        streams = pipe.split(np.concatenate(rows_all, axis=0))
        streams["densities"] = densities  # legacy shape: initial row kept
        observables = streams
    return SimResult(grid=np.asarray(grid), observables=observables,
                     mcs_completed=mcs_done, stasis_mcs=stasis_mcs,
                     kept_fraction=(kept_total / att_total) if att_total else 1.0)


# ----------------------- vmapped IID trial runner ------------------------ #

def run_trials(params: EscgParams, dom: Optional[np.ndarray], n_trials: int,
               key: Optional[jax.Array] = None,
               n_mcs: Optional[int] = None) -> np.ndarray:
    """Back-compat wrapper over the trial subsystem (``core.trials``):
    returns only the final survival mask, shape (n_trials, S) bool.

    The full driver — chunked, device-sharded over the pod axis, streaming
    stasis / extinction statistics — lives in ``trials.run_trials`` and
    returns a ``TrialResult``; prefer it for new code (DESIGN.md §4). The
    trial driver honours ``params.cell_dtype`` (the legacy vmap runner here
    silently initialized int32 lattices regardless).
    """
    from .trials import run_trials as _run_trials  # lazy: avoid cycle
    return _run_trials(params, dom, n_trials, key=key, n_mcs=n_mcs,
                       stop_on_stasis=False).survival
