"""Cells, configurations, traffic mixes, per-layer readers, drivers and
engine reference streams are found by name: a throwaway cell added as
files under a temporary checkout runs without an existing file being
edited, even one with a driver and an engine stream module of its own."""
import hashlib
import json
import os
import textwrap

import numpy as np
import pytest

from bench import cells, drivers, harness, references
from bench.control import control_numbers

from bench_cases import REAL_ROOT, TRIALS, run_cell, small_config


def _tree_digest(root):
    h = hashlib.sha256()
    for base, dirs, files in sorted(os.walk(os.path.join(root, "bench"))):
        dirs[:] = sorted(d for d in dirs if d != "__pycache__")
        for f in sorted(files):
            if f.endswith(".pyc"):
                continue
            with open(os.path.join(base, f), "rb") as fh:
                h.update(f.encode() + fh.read())
    with open(os.path.join(root, "BENCHMARK.json"), "rb") as fh:
        h.update(fh.read())
    return h.hexdigest()


@pytest.fixture
def throwaway(tmp_path):
    """A checkout root whose BENCHMARK.json names only a new cell, with a
    new configuration, a new traffic mix and a new per-layer reader."""
    root = tmp_path
    for sub in ("configs", "traffic", "metrics"):
        (root / "bench" / sub).mkdir(parents=True)
    cfg = small_config(TRIALS, name="throwaway_cfg", trials=3, chunk_mcs=2)
    (root / "bench" / "configs" / "throwaway_cfg.json").write_text(
        json.dumps(cfg))
    traffic = {"name": "throwaway_mix", "engine": "sublattice",
               "window_updates_per_s": 1.0, "check_sample": 3}
    (root / "bench" / "traffic" / "throwaway_mix.json").write_text(
        json.dumps(traffic))
    (root / "bench" / "metrics" / "throwaway_reader.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    bench = {
        "workloads": [{"name": "throwaway_cfg.throwaway_mix",
                       "config": "throwaway_cfg",
                       "traffic": "throwaway_mix", "chips": 1}],
        "end_to_end": [
            {"name": "site_updates_per_s", "unit": "updates/s"},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": [
            {"name": "throwaway_reader", "unit": "x",
             "moves": "site_updates_per_s",
             "workloads": ["throwaway_cfg.throwaway_mix"]},
            {"name": "device_idle_share", "unit": "fraction",
             "moves": "site_updates_per_s",
             "workloads": ["some.other_cell"]}],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


def test_throwaway_cell_is_found_by_name(throwaway):
    cell = cells.load("throwaway_cfg.throwaway_mix", throwaway)
    assert cell.config["name"] == "throwaway_cfg"
    assert cell.traffic["name"] == "throwaway_mix"
    assert cell.chunk_mcs == 2
    assert [m["name"] for m in cell.per_layer()] == ["throwaway_reader"]
    assert harness._reader("throwaway_reader", throwaway)(None) == 42.0
    assert harness._reader("device_idle_share", throwaway) is not None


def test_throwaway_cell_runs_and_edits_nothing(throwaway, capsys):
    before = _tree_digest(REAL_ROOT)
    rc, result = run_cell(throwaway, "throwaway_cfg.throwaway_mix",
                          capsys=capsys)
    assert rc == 0
    assert result["correct"] is True
    assert set(result["metrics"]) == {"site_updates_per_s", "setup_s"}
    assert result["window"]["chunks"] == cells.MIN_WINDOW_CHUNKS
    assert result["window"]["mcs"] == 2 * cells.MIN_WINDOW_CHUNKS
    assert result["window"]["compiles"] == 0
    assert _tree_digest(REAL_ROOT) == before


def test_unknown_cell_names_the_known_ones(throwaway):
    with pytest.raises(KeyError, match="throwaway_cfg.throwaway_mix"):
        cells.load("no_such.cell", throwaway)


def test_every_benchmark_cell_resolves():
    with open(os.path.join(REAL_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = cells.load(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.traffic["name"] == w["traffic"]
        assert drivers.of(cell).__name__.endswith(cell.config["driver"])
        for m in cell.per_layer():
            assert os.path.exists(os.path.join(
                cells.BENCH_DIR, "metrics", f"{m['name']}.py"))
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(REAL_ROOT, c["file"]))


# A sweep-shaped driver: the two halves of the trial batch play two
# parameter points, one run_trials call each from the same seed, so that
# trial i of either half starts from the same lattice and streams. Its
# reference follows each half's sampled trials under that half's rates,
# one call of the shared reference per point.
TWO_POINTS_DRIVER = """
import dataclasses

import jax.numpy as jnp
import numpy as np

from bench import compare, references
from bench.drivers import run_trials as batch

LIMITS = batch.LIMITS
check, answered, as_answers = batch.check, batch.answered, batch.as_answers


def _points(cell):
    half = cell.config["trials"] // 2
    return half, [dict(cell.config, trials=half, **point)
                  for point in cell.config["points"]]


def run(cell, seed, n_mcs, on_boundary):
    half, points = _points(cell)
    first = batch.run(dataclasses.replace(cell, config=points[0]), seed,
                      n_mcs, lambda i: None)[1]
    times, second = batch.run(dataclasses.replace(cell, config=points[1]),
                              seed, n_mcs, on_boundary)
    got = {k: np.concatenate([first[k], second[k]])
           for k in first if k != "mcs_completed"}
    got["mcs_completed"] = min(first["mcs_completed"],
                               second["mcs_completed"])
    return times, got


def reference(cell, seed, n_mcs, dtype=jnp.float32):
    half, points = _points(cell)
    ids = compare.sample_trials(cell.config["trials"],
                                cell.traffic["check_sample"], seed)
    parts = [references.trials(point, cell.traffic["engine"], seed,
                               ids[ids // half == p] % half, n_mcs, dtype,
                               root=cell.root)
             for p, point in enumerate(points)]
    return ids, np.concatenate(parts)
"""

# The Pallas round of the sublattice engine draws the sublattice streams.
PALLAS_STREAMS = """
from bench.references.sublattice import step  # noqa: F401
"""

SWEEP_CELL = "sweep_cfg.sweep_mix"
SWEEP_TRIALS = 8


def _point(alpha, beta):
    """The probabilistic preset's knobs and its dominance edges at
    (alpha, beta): the real configuration's edges, rates replaced."""
    cfg = small_config(TRIALS)
    knobs = dict(cfg["scenario_knobs"], alpha=alpha, beta=beta)
    rate = {cfg["scenario_knobs"]["alpha"]: alpha,
            cfg["scenario_knobs"]["beta"]: beta}
    return {"scenario_knobs": knobs,
            "dominance": [[w, l, rate.get(r, r)]
                          for w, l, r in cfg["dominance"]]}


@pytest.fixture
def sweep_root(tmp_path):
    """A checkout root whose one cell names a new driver and a new engine's
    reference streams, each a file of its own."""
    root = tmp_path
    for sub in ("configs", "traffic", "drivers", "references"):
        (root / "bench" / sub).mkdir(parents=True)
    cfg = small_config(TRIALS, name="sweep_cfg", trials=SWEEP_TRIALS,
                       driver="two_points",
                       points=[_point(0.15, 0.75), _point(0.6, 0.1)])
    (root / "bench" / "configs" / "sweep_cfg.json").write_text(
        json.dumps(cfg))
    traffic = {"name": "sweep_mix", "engine": "pallas",
               "window_updates_per_s": 1.0, "check_sample": SWEEP_TRIALS}
    (root / "bench" / "traffic" / "sweep_mix.json").write_text(
        json.dumps(traffic))
    (root / "bench" / "drivers" / "two_points.py").write_text(
        textwrap.dedent(TWO_POINTS_DRIVER))
    (root / "bench" / "references" / "pallas.py").write_text(
        textwrap.dedent(PALLAS_STREAMS))
    bench = {
        "workloads": [{"name": SWEEP_CELL, "config": "sweep_cfg",
                       "traffic": "sweep_mix", "chips": 1}],
        "end_to_end": [
            {"name": "site_updates_per_s", "unit": "updates/s"},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": [],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


def test_new_driver_and_streams_run_and_edit_nothing(sweep_root, capsys):
    before = _tree_digest(REAL_ROOT)
    cell = cells.load(SWEEP_CELL, sweep_root)
    driver = drivers.of(cell)
    assert driver.__file__.startswith(sweep_root)
    rc, result = run_cell(sweep_root, SWEEP_CELL, capsys=capsys)
    assert rc == 0
    assert result["correct"] is True
    assert result["attempted"] == SWEEP_TRIALS and result["failed"] == 0
    assert result["check"] == {"trials_differing": {"value": 0,
                                                    "limit": 0}}
    numbers = control_numbers(cell, 2**31 + 17, 4)
    assert numbers["trials_differing"] > driver.LIMITS["trials_differing"]
    assert _tree_digest(REAL_ROOT) == before


def test_new_driver_gives_the_halves_their_own_rates(sweep_root):
    """Trial i of each half starts from the same lattice and streams: the
    halves differ only by their rates, and the reference follows each."""
    cell = cells.load(SWEEP_CELL, sweep_root)
    driver = drivers.of(cell)
    _, got = driver.run(cell, 2**31 + 19, 4, lambda i: None)
    half = SWEEP_TRIALS // 2
    first, second = np.split(got["final_counts"], 2)
    assert not np.array_equal(first, second)
    ids, counts = driver.reference(cell, 2**31 + 19, 4)
    assert np.array_equal(got["final_counts"][ids], counts[:, -1])
    # the first point's rates for every trial: the second half is missed
    one_rate = references.trials(cell.config, "pallas", 2**31 + 19,
                                 ids % half, 4, root=sweep_root)
    second_half = ids >= half
    assert np.array_equal(got["final_counts"][ids[~second_half]],
                          one_rate[~second_half, -1])
    assert not np.array_equal(got["final_counts"][ids[second_half]],
                              one_rate[second_half, -1])


def test_unknown_driver_names_the_known_ones(sweep_root):
    with pytest.raises(KeyError, match="no drivers module 'no_such'") as e:
        drivers.find("no_such", sweep_root)
    for known in ("run_trials", "simulate", "two_points"):
        assert known in str(e.value)
    cell = cells.load(SWEEP_CELL, sweep_root)
    cell.config["driver"] = "no_such"
    with pytest.raises(KeyError, match="run_trials"):
        drivers.of(cell)
