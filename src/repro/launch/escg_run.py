"""ESCG simulation driver — CLI-parity with the paper (Tables 3.1/3.2).

This is the production entry point for the paper's own workload: the
end-to-end driver of this framework's kind (simulation). Supports every
registered engine, --save/--resume state round-trips, dominance CSV import,
periodic snapshots and density export.

Beyond the paper's CLI it exposes the two scaling axes and their
composition (DESIGN.md §4-§6):

* ``--engine sharded [--shardGrid R C] [--localKernel pallas|fused]`` —
  one big lattice decomposed across devices (grid axis); ``--localKernel``
  selects the in-region tile sweep implementation: ``jnp``/``pallas`` are
  bit-identical to each other, ``fused`` derives proposals in-kernel from
  Philox counters keyed by global tile identity (zero proposal HBM
  traffic, bit-identical to ``--engine pallas_fused``).
* ``--trials N [--trialDevices D]`` — N IID replicate lattices, vmapped
  and sharded across devices over the trial axis (pod axis). Prints
  streamed survival / stasis statistics; with ``--save true`` the full
  ``TrialResult`` JSON lands in ``<outDir>/trials.json``. Results are
  bit-identical for any ``--trialDevices`` (per-trial fold-in PRNG keys).
* ``--trials N --engine sharded_pod --meshShape P,R,C`` — BOTH axes at
  once on a composed ('pod','rows','cols') mesh: trials shard over the
  pod axis while every trial's lattice is domain-decomposed over
  (rows, cols) with halo exchange. Bit-identical to the single-device
  run for any factorization.

The scenario layer (DESIGN.md §10) makes every registered study a one-flag
invocation: ``--scenario NAME`` pulls species count, dominance network,
action rates and boundary condition from the scenario registry
(``core/scenarios.py``); explicitly-passed physics flags override the
preset, and parametric families take a numeric suffix (``nspecies7``).
``--listScenarios [--markdown|--check README.md]`` prints/CI-checks the
registry-generated scenario matrix, exactly like ``--listEngines`` does
for engines.

Examples:
  python -m repro.launch.escg_run --scenario zhong_density --mcs 1000 \
      --length 64 --height 64          # Zhong ablated RPSLS, one flag
  python -m repro.launch.escg_run --scenario probabilistic --trials 64 \
      --mcs 10000                      # Park alliances, massed replication
  python -m repro.launch.escg_run --scenario nspecies7 --mcs 2000 \
      --engine sublattice --tile 8 16  # 7-species cyclic family
  python -m repro.launch.escg_run --listScenarios --markdown
  python -m repro.launch.escg_run --length 200 --height 200 --mcs 2000 \
      --engine batched --save true --outDir out/rps
  python -m repro.launch.escg_run --dominance dominance.csv --resume true \
      --outDir out/rps            # continue a saved run
  python -m repro.launch.escg_run --length 100 --height 100 --species 8 \
      --trials 64 --mcs 10000     # Park-style massed IID replication
  python -m repro.launch.escg_run --length 800 --height 800 --species 8 \
      --trials 16 --mcs 10000 --engine sharded_pod --meshShape 4,2,2 \
      --tile 8 32                 # massed replication of LARGE lattices
  python -m repro.launch.escg_run --length 800 --height 800 --species 8 \
      --trials 16 --mcs 10000 --engine sharded_pod --meshShape 4,2,2 \
      --tile 8 32 --localKernel fused   # same, zero proposal HBM traffic
  python -m repro.launch.escg_run --listEngines --markdown   # engine matrix
"""
from __future__ import annotations

import argparse
import os
import re
import time
from typing import Optional, Sequence

import jax
import numpy as np

from ..core import dominance as dom_mod
from ..core import engines, scenarios, tracing
from ..core import io as io_mod
from ..core.params import EscgParams, add_cli_args, params_from_args
from ..core.simulation import simulate
from ..core.trials import run_trials
from .compile_cache import enable_compile_cache

# ---------------------- registry matrices (docs) -------------------------- #
# Both README tables — engines and scenarios — are generated from their
# registries and CI-checked against drift with the same marker mechanism.

_MATRIX_HEAD = ("engine", "boundaries", "tile", "devices", "trial axis",
                "local kernels", "reproduces")
_MATRIX_BEGIN = ("<!-- engine-matrix:begin (generated: escg_run "
                 "--listEngines --markdown; CI-checked) -->")
_MATRIX_END = "<!-- engine-matrix:end -->"

_SC_MATRIX_HEAD = ("scenario", "species", "rates", "boundary", "init",
                   "observables", "reproduces")
_SC_MATRIX_BEGIN = ("<!-- scenario-matrix:begin (generated: escg_run "
                    "--listScenarios --markdown; CI-checked) -->")
_SC_MATRIX_END = "<!-- scenario-matrix:end -->"


def _markdown_table(head, rows) -> str:
    lines = ["| " + " | ".join(head) + " |", "|" + "---|" * len(head)]
    lines += ["| " + " | ".join(row) + " |" for row in rows]
    return "\n".join(lines)


def _readme_block_drift(readme_path: str, begin: str, end: str, want: str,
                        what: str, regen_flag: str) -> Optional[str]:
    """None when the README block between ``begin``/``end`` equals the
    registry-generated table; else a human-readable drift message."""
    with open(readme_path) as f:
        text = f.read()
    m = re.search(re.escape(begin) + r"\n(.*?)\n" + re.escape(end),
                  text, re.S)
    if not m:
        return f"{readme_path}: {what} markers not found"
    got = m.group(1).strip()
    if got != want.strip():
        return (f"{readme_path}: {what} drifted from the registry.\n"
                f"Regenerate with:\n  PYTHONPATH=src python -m "
                f"repro.launch.escg_run {regen_flag} --markdown\n"
                f"--- README ---\n{got}\n--- registry ---\n{want.strip()}")
    return None


def engine_matrix_rows():
    """One row per registered engine, derived purely from EngineCaps."""
    rows = []
    for spec in engines.engine_specs():
        c = spec.caps
        tile = ("must divide device blocks" if c.multi_device
                else "must divide lattice") if c.tiled else "—"
        rows.append((f"`{spec.name}`",
                     "flux only" if c.flux_only else "flux or reflect",
                     tile,
                     "multi" if c.multi_device else "single",
                     c.trial_axis,
                     ", ".join(f"`{k}`" for k in c.local_kernels) or "—",
                     f"{c.paper} — {c.description}"))
    return rows


def engine_matrix_markdown() -> str:
    """The README engine matrix, generated from the live registry."""
    return _markdown_table(_MATRIX_HEAD, engine_matrix_rows())


def readme_matrix_drift(readme_path: str) -> Optional[str]:
    """Engine-matrix drift check: used by ``--listEngines --check`` (CI)
    and tests/test_docs.py."""
    return _readme_block_drift(readme_path, _MATRIX_BEGIN, _MATRIX_END,
                               engine_matrix_markdown(), "engine matrix",
                               "--listEngines")


def scenario_matrix_rows():
    """One row per registered scenario, derived from ScenarioCaps."""
    rows = []
    for spec in scenarios.scenario_specs():
        c = spec.caps
        rows.append((f"`{spec.name}`",
                     "parametric (`S`)" if c.species is None
                     else str(c.species),
                     c.rates,
                     c.boundary,
                     c.init,
                     ", ".join(f"`{o}`" for o in c.observables) or "—",
                     f"{c.paper} — {c.description}"))
    return rows


def scenario_matrix_markdown() -> str:
    """The README scenario matrix, generated from the live registry."""
    return _markdown_table(_SC_MATRIX_HEAD, scenario_matrix_rows())


def readme_scenario_drift(readme_path: str) -> Optional[str]:
    """Scenario-matrix drift check: used by ``--listScenarios --check``
    (CI) and tests/test_docs.py."""
    return _readme_block_drift(readme_path, _SC_MATRIX_BEGIN,
                               _SC_MATRIX_END, scenario_matrix_markdown(),
                               "scenario matrix", "--listScenarios")


def print_engine_matrix() -> None:
    """Registry-driven engine table (plain-text variant)."""
    print(f"{'engine':<13} {'boundaries':<11} {'tiled':<6} {'devices':<8} "
          f"{'trial axis':<17} paper ref")
    for spec in engines.engine_specs():
        c = spec.caps
        print(f"{spec.name:<13} {'flux-only' if c.flux_only else 'any':<11} "
              f"{'yes' if c.tiled else 'no':<6} "
              f"{'multi' if c.multi_device else 'single':<8} "
              f"{c.trial_axis:<17} {c.paper}")
        print(f"{'':13} {spec.caps.description}")


def print_scenario_matrix() -> None:
    """Registry-driven scenario table (plain-text variant)."""
    print(f"{'scenario':<15} {'species':<9} {'rates':<14} {'boundary':<9} "
          "paper ref")
    for spec in scenarios.scenario_specs():
        c = spec.caps
        sp = "S (param)" if c.species is None else str(c.species)
        print(f"{spec.name:<15} {sp:<9} {c.rates:<14} {c.boundary:<9} "
              f"{c.paper}")
        print(f"{'':15} {c.description}")


def chunk_rates(updates_per_mcs: int) -> str:
    """The last run's speed from its chunk records (``core/tracing.py``):
    the first chunk's seconds, which hold set-up, tracing and compiling,
    on their own, then the update rate over the chunks after it."""
    run = tracing.last_run()
    if run is None or run.first is None:
        return "no chunk ran"
    first, last = run.first, run.chunks[-1]
    first_s = first.end_s - run.start_s
    if last is first:
        return (f"one chunk, {first.mcs * updates_per_mcs / first_s:.3g} "
                "updates/s with its compile included")
    later_mcs, later_s = last.mcs - first.mcs, last.end_s - first.end_s
    return (f"first chunk {first_s:.2f}s with its compile, then "
            f"{later_mcs * updates_per_mcs / later_s:.3g} updates/s over "
            f"{later_mcs} MCS")


# ------------------------------ trial mode -------------------------------- #

def run_trial_batch(params: EscgParams, dom: np.ndarray, n_trials: int,
                    trial_devices: Optional[int]) -> None:
    """--trials N: massed IID replication through the pod-axis driver."""
    def progress(mcs_done, alive_counts):
        in_stasis = int((alive_counts <= 1).sum())
        print(f"[escg]   chunk -> MCS {mcs_done}: {in_stasis}/{n_trials} "
              f"trials in stasis", flush=True)

    # scenario-first call form (DESIGN.md §10): the resolved params split
    # back into layers; the explicit run.observables tuple round-trips, so
    # composing reproduces `params` exactly
    sc, eng_cfg, run_cfg = scenarios.decompose(params)
    t0 = time.time()
    res = run_trials(sc, dom, n_trials, trial_devices=trial_devices,
                     hooks=[progress], engine=eng_cfg, run=run_cfg)
    dt = time.time() - t0

    print(f"[escg] {n_trials} trials x {params.height}x{params.length} "
          f"species={params.species} engine={params.engine} on "
          f"{res.n_devices} device(s): {res.mcs_completed} MCS in {dt:.2f}s "
          f"({chunk_rates(params.n_cells * n_trials)})")
    print(f"[escg] survival probabilities: "
          f"{np.round(res.survival_probabilities(), 4)}")
    print(f"[escg] survivors histogram:    "
          f"{np.round(res.survivors_hist(), 4)}")
    n_stasis = int((res.stasis_mcs >= 0).sum())
    if n_stasis:
        reached = res.stasis_mcs[res.stasis_mcs >= 0]
        print(f"[escg] stasis reached in {n_stasis}/{n_trials} trials "
              f"(median MCS {int(np.median(reached))})")
    if params.save:
        os.makedirs(params.out_dir, exist_ok=True)
        path = os.path.join(params.out_dir, "trials.json")
        with open(path, "w") as f:
            f.write(res.to_json())
        print(f"[escg] TrialResult saved to {path}")


# --------------------------------- main ----------------------------------- #

def build_parser() -> argparse.ArgumentParser:
    """The full CLI parser (paper flags + scaling + scenario layer) —
    exposed so tests can drive the exact ``--scenario`` resolution path."""
    ap = argparse.ArgumentParser(description="ESCG simulator (paper CLI)")
    add_cli_args(ap)
    ap.add_argument("--snapshotEvery", dest="snapshot_every", type=int,
                    default=0, help="save lattice snapshot every N MCS")
    ap.add_argument("--trials", type=int, default=0,
                    help="run N IID trials (vmapped, sharded across devices "
                         "over the trial axis) instead of one simulation; "
                         "prints survival/stasis statistics")
    ap.add_argument("--trialDevices", dest="trial_devices", type=int,
                    default=None,
                    help="pod width for --trials: number of local devices "
                         "to shard the trial axis across (default: all; "
                         "results are bit-identical for any value)")
    ap.add_argument("--scenario", type=str, default=None,
                    help="run a registered scenario preset (see "
                         "--listScenarios); its physics — species, "
                         "dominance network, rates, boundary — come from "
                         "the registry, overridden by explicitly-passed "
                         "flags; parametric families take a numeric "
                         "suffix (nspecies7)")
    ap.add_argument("--listEngines", dest="list_engines",
                    action="store_true",
                    help="print the registered engine matrix and exit")
    ap.add_argument("--listScenarios", dest="list_scenarios",
                    action="store_true",
                    help="print the registered scenario matrix and exit")
    ap.add_argument("--markdown", action="store_true",
                    help="with --listEngines/--listScenarios: print the "
                         "matrix as the markdown table embedded in "
                         "README.md")
    ap.add_argument("--check", dest="check_readme", metavar="README",
                    default=None,
                    help="with --listEngines/--listScenarios: exit "
                         "non-zero if README's matrix drifted from the "
                         "registry (CI)")
    return ap


def scenario_setup(args, ap: argparse.ArgumentParser):
    """Resolve ``--scenario``: (validated EscgParams, dominance matrix).
    Physics come from the registry preset, overridden by explicitly-passed
    scenario flags; engine/run control from the remaining CLI flags.
    Resolution goes through ``scenarios.resolve_config``, so the preset's
    ``ScenarioCaps.observables`` stream by default (DESIGN.md §11) unless
    ``--observables`` pins the set ('none' disables)."""
    sc = scenarios.scenario_from_cli(args, ap)
    params, dom = scenarios.resolve_config(
        sc, None, scenarios.engine_config_from_args(args),
        scenarios.run_config_from_args(args))
    return sc, params, dom


def main(argv: Optional[Sequence[str]] = None) -> None:
    ap = build_parser()
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.list_engines or args.list_scenarios:
        for flagged, drift_fn, md_fn, text_fn, what in (
                (args.list_engines, readme_matrix_drift,
                 engine_matrix_markdown, print_engine_matrix,
                 "engine matrix"),
                (args.list_scenarios, readme_scenario_drift,
                 scenario_matrix_markdown, print_scenario_matrix,
                 "scenario matrix")):
            if not flagged:
                continue
            if args.check_readme:
                drift = drift_fn(args.check_readme)
                if drift:
                    raise SystemExit(drift)
                print(f"[escg] {args.check_readme} {what} matches the "
                      "registry")
            elif args.markdown:
                print(md_fn())
            else:
                text_fn()
        return

    grid0 = None
    key = None
    start_mcs = 0
    if args.resume:
        if args.trials:
            raise SystemExit("--trials and --resume are mutually exclusive "
                             "(trial batches keep no host-side state)")
        if args.scenario:
            raise SystemExit("--scenario and --resume are mutually "
                             "exclusive (the resumed state already "
                             "carries its physics)")
        params, grid0, start_mcs, dom, key_arr = io_mod.load_state(
            args.out_dir)
        params = params.replace(resume=True)
        key = (jax.numpy.asarray(key_arr) if key_arr is not None
               else jax.random.fold_in(jax.random.PRNGKey(params.seed),
                                       start_mcs))
        # allow the CLI to extend the run beyond the saved target
        params = params.replace(mcs=max(params.mcs, args.mcs))
        print(f"[escg] resumed {args.out_dir} at MCS {start_mcs}")
    elif args.scenario:
        # scenario layer (DESIGN.md §10): physics from the registry,
        # engine/run control from the CLI; explicitly-passed scenario
        # flags (--species, --mobility, ...) override the preset
        if args.dominance:
            raise SystemExit("--scenario and --dominance are mutually "
                             "exclusive (the scenario defines its own "
                             "dominance network)")
        sc, params, dom = scenario_setup(args, ap)
        print(f"[escg] scenario {sc.name!r}: species={sc.species} "
              f"rates={scenarios.get_scenario(sc.name).caps.rates} "
              f"boundary={sc.boundary}")
    else:
        params = params_from_args(args)
        if args.dominance:
            with open(args.dominance) as f:
                dom = dom_mod.from_csv(f.read())
            params = params.replace(species=dom.shape[0] - 1)
        else:
            # default circulant: RPS for 3, C(S,{1,2}) for 5+, C(S,{1}) else
            offs = (1, 2) if params.species >= 5 else (1,)
            dom = dom_mod.circulant(params.species, offs)

    if args.trials:
        run_trial_batch(params.validate(), dom, args.trials,
                        args.trial_devices)
        return

    params = params.replace(mcs=params.mcs - start_mcs).validate()

    hooks = []
    if args.snapshot_every:
        def snap_hook(mcs_done, grid, cnts):
            if mcs_done % args.snapshot_every == 0:
                io_mod.save_snapshot(params.out_dir, np.asarray(grid),
                                     start_mcs + mcs_done)
        hooks.append(snap_hook)

    if params.print_frequency > 0:
        # periodic density print (paper printFrequency). The per-MCS rows
        # arrive once per chunk — flushed from the device observable ring
        # when the pipeline is on (DESIGN.md §11) — so printing any
        # interval costs zero extra host transfers.
        pf, n_cells = params.print_frequency, params.n_cells

        def density_hook(mcs_done, grid, cnts):
            first = mcs_done - len(cnts) + 1
            for i in range((-first % pf), len(cnts), pf):
                print(f"[escg] MCS {start_mcs + first + i}: densities "
                      f"{np.round(cnts[i] / n_cells, 4)}", flush=True)
        hooks.append(density_hook)

    # scenario-first call form (DESIGN.md §10); decompose round-trips the
    # resolved params exactly, observables included
    sc_run, eng_cfg, run_cfg = scenarios.decompose(params)
    t0 = time.time()
    res = simulate(sc_run, dom, grid0=grid0, key=key, hooks=hooks,
                   engine=eng_cfg, run=run_cfg)
    dt = time.time() - t0

    n = params.n_cells
    total_mcs = start_mcs + res.mcs_completed
    print(f"[escg] {params.height}x{params.length} species={params.species}"
          f" engine={params.engine}: {res.mcs_completed} MCS in {dt:.2f}s"
          f" ({chunk_rates(n)})")
    if res.stasis_mcs >= 0:
        print(f"[escg] stasis (monoculture/dead) at MCS "
              f"{start_mcs + res.stasis_mcs}")
    print("[escg] final densities:", np.round(res.densities[-1], 4))

    if params.save:
        os.makedirs(params.out_dir, exist_ok=True)
        io_mod.save_state(params.out_dir, params.replace(mcs=args.mcs),
                          res.grid, total_mcs, np.asarray(dom))
        io_mod.export_densities_csv(
            os.path.join(params.out_dir, "densities.csv"), res.densities)
        print(f"[escg] state + densities saved to {params.out_dir}")


if __name__ == "__main__":
    main()
