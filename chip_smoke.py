#!/usr/bin/env python3
"""Smoke run of the ESCG system on a TPU chip, through its user entry points.

    python chip_smoke.py                 # one chip: every single-chip phase
    python chip_smoke.py --four-chips    # four chips: the mesh engines only

One process, no child touches JAX. Without a TPU it exits non-zero at once.
Each phase prints one ``[phase]`` JSON line with its compile seconds, run
seconds, smoke updates/s (a smoke figure: it is not a benchmark) and the
device's peak memory. A phase that raises or answers with an error makes
the script exit non-zero. The last line of a passing run is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Single chip: park3 at 3200x3200 (the paper's largest lattice) through
``simulate`` on the sublattice, pallas and pallas_fused engines, with pallas
bit-identical to sublattice; the fused kernel against its host-Philox
oracle; the k_mcs megakernel at its largest accepted lattice against K
single rounds; the Table 4.2 replication protocol (probabilistic, L=200,
1024 trials) through ``escg_run.main``; a pallas_fused trial batch; and
``escg_serve`` over a two-wave trace with ``--check``.

Four chips: the sharded engine on a 2x2 grid at 3200x3200 for every local
kernel, and sharded_pod trial batches on (4,1,1) and (1,2,2) meshes, each
bit-identical to its one-device oracle.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import math
import os
import re
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "out", "chip_smoke")

L = 3200                 # the paper's largest lattice (Fig 4.3)
TILE = (8, 32)           # the engines' default tile
ORACLE_L = 512           # fused kernel vs host Philox
REPL_L, REPL_TILE, REPL_TRIALS = 200, (8, 25), 1024   # Table 4.2 protocol
BATCH_L, BATCH_TRIALS = 256, 32       # pallas_fused trial batch
SERVE_L, SERVE_TRIALS = 1024, 256     # served park3 lattice, served trials
# XLA compiles, or their persistent-cache reads (tracing events nest, so
# they are left in the run time)
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


class PhaseFailed(Exception):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


class Smoke:
    """Runs phases, times them and collects failures."""

    def __init__(self, jax):
        self.jax = jax
        self.compile_s = 0.0
        self.failed = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.compile_s += duration

    def phase(self, name, fn):
        c0, t0 = self.compile_s, time.perf_counter()
        row = {"phase": name}
        try:
            info = fn() or {}
        except Exception as e:  # noqa: BLE001 - report every phase
            traceback.print_exc()
            self.failed.append(name)
            row.update(ok=False, error=f"{type(e).__name__}: {e}")
            print(json.dumps(row), flush=True)
            return
        wall = time.perf_counter() - t0
        compile_s = self.compile_s - c0
        run_s = wall - compile_s
        stats = self.jax.devices()[0].memory_stats() or {}
        row.update(ok=True, compile_s=compile_s, run_s=run_s,
                   peak_bytes_in_use=stats.get("peak_bytes_in_use"))
        if info.get("updates"):
            row["smoke_updates_per_s"] = info.pop("updates") / run_s
        row.update(info)
        print(json.dumps(row), flush=True)


def compiled_has_kernel(fn, *args) -> bool:
    import jax
    return "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


# ------------------------------ single chip ------------------------------- #

def phase_lattice():
    """park3 at 3200x3200 through simulate: sublattice, pallas, fused."""
    import jax
    import numpy as np
    from repro.core import engines, simulate
    from repro.core.scenarios import (EngineConfig, RunConfig, compose,
                                      make_scenario)

    sc = make_scenario("park3")
    run = RunConfig(height=L, length=L, mcs=4, chunk_mcs=2, seed=0)
    results, seconds = {}, {}
    for engine in ("sublattice", "pallas", "pallas_fused"):
        eng = EngineConfig(engine=engine, tile=TILE)
        if engine != "sublattice":
            built = engines.build(compose(sc, eng, run))
            grid = jax.ShapeDtypeStruct((L, L), np.int32)
            check(compiled_has_kernel(built.one_mcs, grid,
                                      jax.random.PRNGKey(0)),
                  f"{engine}: no tpu_custom_call in the compiled step")
        t0 = time.perf_counter()
        res = simulate(sc, engine=eng, run=run, stop_on_stasis=False)
        seconds[engine] = time.perf_counter() - t0
        dens = np.asarray(res.densities)
        check(res.mcs_completed == run.mcs, f"{engine}: ran "
              f"{res.mcs_completed} of {run.mcs} MCS")
        check(dens.shape == (run.mcs + 1, sc.species + 1)
              and np.all(np.isfinite(dens))
              and np.allclose(dens.sum(axis=1), 1.0),
              f"{engine}: densities malformed")
        grid = np.asarray(res.grid)
        check(grid.min() >= 0 and grid.max() <= sc.species,
              f"{engine}: cell values out of range")
        results[engine] = res
    check(np.array_equal(np.asarray(results["pallas"].grid),
                         np.asarray(results["sublattice"].grid)),
          "pallas lattice differs from sublattice")
    check(np.array_equal(results["pallas"].densities,
                         results["sublattice"].densities),
          "pallas densities differ from sublattice")
    return {"updates": 3 * run.mcs * L * L, "lattice": [L, L],
            "simulate_wall_s_incl_compile": seconds,
            "pallas_equals_sublattice": True}


def phase_fused_oracle():
    """One fused round at 512x512 against host Philox + the tile oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import EscgParams, dominance as dm
    from repro.core.engines import _tiled_setup
    from repro.core.lattice import init_grid
    from repro.kernels import ops, ref

    h = w = ORACLE_L
    _, _, n_tiles, k, interior = _tiled_setup(
        EscgParams(height=h, length=w, tile=TILE))
    dom = jnp.asarray(dm.RPSLS())
    grid = init_grid(jax.random.PRNGKey(3), h, w, 5, 0.1)
    seed = (0xABCD1234, 0x5678DEAD)
    shift = jnp.array([3, 17], jnp.int32)
    got = ops.escg_round_fused(grid, jnp.asarray(np.array(seed, np.uint32)),
                               jnp.uint32(7), shift, dom, TILE, k, 0.25,
                               0.6, 4)
    cell, dirn, ua, ud = ref.fused_proposals_ref(n_tiles, k, interior, 4,
                                                 seed, 7)
    want = ref.escg_tile_round_ref(
        jnp.roll(grid, (-3, -17), (0, 1)), jnp.asarray(cell),
        jnp.asarray(dirn), jnp.asarray(ua), jnp.asarray(ud), dom, TILE,
        0.25, 0.6)
    want = jnp.roll(want, (3, 17), (0, 1))
    check(np.array_equal(np.asarray(got), np.asarray(want)),
          "pallas_fused round differs from its host-Philox oracle")
    return {"updates": n_tiles * k, "lattice": [h, w],
            "fused_equals_oracle": True}


def phase_megakernel():
    """k_mcs megakernel at its largest accepted lattice vs K rounds."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import EscgParams, dominance as dm, engines, metrics
    from repro.core.engines import _tiled_setup, multi_round_inputs
    from repro.core.lattice import init_grid
    from repro.kernels import escg_update_fused, ops

    side = math.isqrt(escg_update_fused.MEGA_LATTICE_BUDGET_BYTES // 8)
    side -= side % TILE[1]
    steps = 2
    p = EscgParams(height=side, length=side, tile=TILE, species=3,
                   engine="pallas_fused", k_mcs=steps).validate()
    _, _, n_tiles, k, _ = _tiled_setup(p)
    built = engines.build(p, dm.RPS())
    grid_sds = jax.ShapeDtypeStruct((side, side), np.int32)
    check(compiled_has_kernel(lambda g, key: built.multi_mcs(g, key, steps),
                              grid_sds, jax.random.PRNGKey(0)),
          "megakernel: no tpu_custom_call in the compiled step")

    dom = jnp.asarray(dm.RPS())
    grid = init_grid(jax.random.PRNGKey(5), side, side, 3, 0.1)
    _, seeds, shifts = multi_round_inputs(jax.random.PRNGKey(9), *TILE,
                                          steps)
    t_eps, t_eps_mu = p.action_thresholds()
    got_g, got_c = ops.escg_rounds_fused(grid, seeds, shifts, dom, TILE, k,
                                         t_eps, t_eps_mu, 3)
    g = grid
    for t in range(steps):
        g = ops.escg_round_fused(g, seeds[t], jnp.uint32(0), shifts[t], dom,
                                 TILE, k, t_eps, t_eps_mu, 4,
                                 roll_back=False)
        check(np.array_equal(np.asarray(got_c[t]),
                             np.asarray(metrics.counts(g, 3))),
              f"megakernel counts differ at step {t}")
    check(np.array_equal(np.asarray(got_g), np.asarray(g)),
          "megakernel lattice differs from K single rounds")
    return {"updates": 2 * steps * n_tiles * k, "lattice": [side, side],
            "k_steps": steps, "megakernel_equals_rounds": True}


def phase_replication():
    """The Table 4.2 protocol size through the escg_run CLI; its first
    trials must equal a small batch of the same seed (per-trial keys)."""
    import numpy as np
    from repro.core.trials import TrialResult
    from repro.launch import escg_run

    mcs = 6
    results, summary = {}, ""
    for n_trials in (REPL_TRIALS, 4):
        out_dir = os.path.join(OUT_DIR, f"replication_{n_trials}")
        argv = ["--scenario", "probabilistic", "--length", str(REPL_L),
                "--height", str(REPL_L), "--trials", str(n_trials),
                "--trialDevices", "1", "--mcs", str(mcs), "--chunkMcs", "2",
                "--engine", "sublattice", "--tile", *map(str, REPL_TILE),
                "--seed", "1", "--save", "true", "--outDir", out_dir]
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            escg_run.main(argv)
        sys.stdout.write(out.getvalue())
        summary = summary or out.getvalue()
        with open(os.path.join(out_dir, "trials.json"),
                  encoding="utf-8") as f:
            results[n_trials] = TrialResult.from_json(f.read())
    big, small = results[REPL_TRIALS], results[4]
    check(big.n_trials == REPL_TRIALS and big.mcs_completed >= 1,
          "escg_run ran no replication")
    check(re.search(r"survival probabilities: \[", summary) is not None,
          "escg_run printed no survival probabilities")
    check(np.all(np.isfinite(big.densities))
          and np.allclose(big.densities.sum(axis=1), 1.0),
          "trial densities malformed")
    for f in ("survival", "densities", "stasis_mcs", "extinction_mcs"):
        check(np.array_equal(getattr(big, f)[:4], getattr(small, f)),
              f"trials 0-3 of the {REPL_TRIALS}-trial batch differ from a "
              f"4-trial batch in {f}")
    return {"updates": big.mcs_completed * REPL_L ** 2 * REPL_TRIALS,
            "trials": REPL_TRIALS, "mcs": big.mcs_completed,
            "batch_equals_small_batch": True}


def phase_fused_trials():
    """A pallas_fused trial batch: k_mcs=1 and the k_mcs=2 megakernel."""
    import jax
    import numpy as np
    from repro.core import engines
    from repro.core.scenarios import (EngineConfig, RunConfig, compose,
                                      make_scenario)
    from repro.core.trials import run_trials

    sc = make_scenario("nspecies5")
    run = RunConfig(height=BATCH_L, length=BATCH_L, mcs=4, chunk_mcs=2,
                    seed=4)
    n_trials = BATCH_TRIALS
    out = {}
    for k_mcs in (1, 2):
        eng = EngineConfig(engine="pallas_fused", tile=TILE, k_mcs=k_mcs)
        built = engines.build(compose(sc, eng, run))
        grids = jax.ShapeDtypeStruct((n_trials, BATCH_L, BATCH_L),
                                     np.int32)
        keys = jax.ShapeDtypeStruct((n_trials, 2), np.uint32)
        check(compiled_has_kernel(jax.vmap(built.one_mcs), grids, keys),
              "pallas_fused trial batch: no tpu_custom_call")
        out[k_mcs] = run_trials(sc, n_trials=n_trials, engine=eng, run=run,
                                trial_devices=1, stop_on_stasis=False)
    a, b = out[1], out[2]
    check(a.mcs_completed == run.mcs, "trial batch stopped early")
    check(np.all(np.isfinite(a.densities))
          and np.allclose(a.densities.sum(axis=1), 1.0),
          "trial densities malformed")
    for f in ("survival", "densities", "stasis_mcs", "extinction_mcs"):
        check(np.array_equal(getattr(a, f), getattr(b, f)),
              f"k_mcs=2 trial batch differs from k_mcs=1 in {f}")
    return {"updates": 2 * run.mcs * BATCH_L ** 2 * n_trials,
            "trials": n_trials, "k_mcs_2_equals_1": True}


def phase_serve():
    """escg_serve over a two-wave trace at deployment size, --check."""
    from repro.launch import serve

    os.makedirs(OUT_DIR, exist_ok=True)
    trace = os.path.join(OUT_DIR, "serve_trace.jsonl")
    report_path = os.path.join(OUT_DIR, "serve_report.json")
    reqs = [
        {"id": "probabilistic", "n_trials": SERVE_TRIALS,
         "scenario": "probabilistic",
         "engine": {"engine": "sublattice", "tile": list(REPL_TILE)},
         "run": {"height": REPL_L, "length": REPL_L, "mcs": 4,
                 "chunk_mcs": 2, "seed": 1}},
        {"id": "park3", "n_trials": 1, "scenario": "park3",
         "engine": {"engine": "pallas_fused", "tile": list(TILE)},
         "run": {"height": SERVE_L, "length": SERVE_L, "mcs": 4,
                 "chunk_mcs": 2, "seed": 2}},
    ]
    with open(trace, "w", encoding="utf-8") as f:
        for r in reqs:
            f.write(json.dumps(r) + "\n")
    rc = serve.main(["--trace", trace, "--waves", "2", "--maxBatchTrials",
                     str(SERVE_TRIALS), "--report", report_path, "--check"])
    with open(report_path, encoding="utf-8") as f:
        report = json.load(f)
    check(report["n_error"] == 0, f"n_error={report['n_error']}")
    check(report["dropped"] == 0, f"dropped={report['dropped']}")
    check(report["cache"]["hits"] >= 1, "no compiled-engine cache hit")
    check(rc == 0, f"escg_serve --check exited {rc}")
    return {"updates": report["updates"], "requests": report["n_requests"],
            "cache_hits": report["cache"]["hits"]}


# ------------------------------ four chips -------------------------------- #

def _describe_sharding(sharding) -> str:
    devs = sorted(d.id for d in sharding.device_set)
    return f"{sharding} on devices {devs}"


def phase_sharded_grid():
    """sharded on 2x2 at 3200x3200, every local kernel, vs one device."""
    import numpy as np
    from repro.core import engines, simulate
    from repro.core.scenarios import (EngineConfig, RunConfig, compose,
                                      make_scenario)

    sc = make_scenario("park3")
    run = RunConfig(height=L, length=L, mcs=2, chunk_mcs=1, seed=6)
    oracle = {}
    for engine in ("sublattice", "pallas_fused"):
        res = simulate(sc, engine=EngineConfig(engine=engine, tile=TILE),
                       run=run, stop_on_stasis=False)
        oracle[engine] = res
    shardings = {}
    for lk, want in (("jnp", "sublattice"), ("pallas", "sublattice"),
                     ("fused", "pallas_fused")):
        eng = EngineConfig(engine="sharded", tile=TILE, shard_grid=(2, 2),
                           local_kernel=lk)
        built = engines.build(compose(sc, eng, run))
        check(len(built.grid_sharding.device_set) == 4,
              f"sharded/{lk}: lattice is not spread over four devices")
        shardings[lk] = _describe_sharding(built.grid_sharding)
        print(f"[sharded/{lk}] lattice sharding: {shardings[lk]}",
              flush=True)
        res = simulate(sc, engine=eng, run=run, stop_on_stasis=False)
        check(np.array_equal(np.asarray(res.grid),
                             np.asarray(oracle[want].grid)),
              f"sharded/{lk} lattice differs from one-device {want}")
        check(np.array_equal(res.densities, oracle[want].densities),
              f"sharded/{lk} densities differ from one-device {want}")
    return {"updates": 5 * run.mcs * L * L, "lattice": [L, L],
            "shardings": shardings, "bit_identical": True}


def phase_pod_trials():
    """run_trials on sharded_pod (4,1,1) and (1,2,2) vs one device."""
    import numpy as np
    from repro.core import engines
    from repro.core.scenarios import (EngineConfig, RunConfig, compose,
                                      make_scenario)
    from repro.core.trials import run_trials

    sc = make_scenario("probabilistic")
    run = RunConfig(height=BATCH_L, length=BATCH_L, mcs=4, chunk_mcs=2,
                    seed=8)
    n_trials = 16
    want = run_trials(sc, n_trials=n_trials,
                      engine=EngineConfig(engine="sublattice", tile=TILE),
                      run=run, trial_devices=1, stop_on_stasis=False)
    check(want.n_devices == 1, "the oracle batch used more than one device")
    shardings = {}
    for layout in ((4, 1, 1), (1, 2, 2)):
        eng = EngineConfig(engine="sharded_pod", tile=TILE,
                           mesh_shape=layout, local_kernel="jnp")
        built = engines.build(compose(sc, eng, run))
        check(len(built.batch_sharding.device_set) == 4,
              f"sharded_pod {layout}: batch is not spread over four devices")
        shardings[str(layout)] = _describe_sharding(built.batch_sharding)
        print(f"[sharded_pod {layout}] batch sharding: "
              f"{shardings[str(layout)]}", flush=True)
        got = run_trials(sc, n_trials=n_trials, engine=eng, run=run,
                         stop_on_stasis=False)
        check(got.n_devices == 4, f"sharded_pod {layout} ran on "
              f"{got.n_devices} devices")
        for f in ("survival", "densities", "stasis_mcs", "extinction_mcs"):
            check(np.array_equal(getattr(got, f), getattr(want, f)),
                  f"sharded_pod {layout} differs from one device in {f}")
    return {"updates": 3 * run.mcs * BATCH_L ** 2 * n_trials,
            "trials": n_trials, "shardings": shardings,
            "bit_identical": True}


# --------------------------------- main ----------------------------------- #

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip mesh phases")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is "
              f"{dev.platform!r}); nothing was run", file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(jax.devices()) < want:
        print(f"chip_smoke: needs {want} TPU devices, found "
              f"{len(jax.devices())}", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script "
              f"({e})", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    print(json.dumps({"device": dev.device_kind, "count": len(jax.devices()),
                      "jax": jax.__version__, "compile_cache": cache_dir}),
          flush=True)

    smoke = Smoke(jax)
    if args.four_chips:
        phases = (("sharded_2x2_3200", phase_sharded_grid),
                  ("sharded_pod_trials", phase_pod_trials))
    else:
        phases = (("lattice_3200", phase_lattice),
                  ("fused_oracle_512", phase_fused_oracle),
                  ("megakernel_largest", phase_megakernel),
                  ("replication_table4_2", phase_replication),
                  ("fused_trial_batch", phase_fused_trials),
                  ("serve_two_waves", phase_serve))
    t0 = time.perf_counter()
    for name, fn in phases:
        smoke.phase(name, fn)
    print(json.dumps({"total_s": time.perf_counter() - t0,
                      "compile_s": smoke.compile_s,
                      "failed": smoke.failed}), flush=True)
    if smoke.failed:
        print(f"chip_smoke: FAILED phases: {', '.join(smoke.failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
