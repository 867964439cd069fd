"""The sweep cell and the jnp cell at 3200x3200, at small sizes on the CPU:
the sweep driver against the plain reference point by point, whole runs
through the harness, sound and with the timed path broken underneath, and
the control of the sweep's comparison."""
import json
import os

import numpy as np
import pytest

from bench_cases import LATTICE, real_config, run_cell, small_root

from bench import cells, drivers, references
from bench.cells import Cell
from bench.control import control_numbers
from repro.core import engines, scenarios

SWEEP = "probabilistic_sweep8_L200_x250"
SWEEP_SIZE = {"height": 16, "length": 50, "trials": 16}    # 2 per point
CELLS = {"sweep": f"{SWEEP}.sublattice", "lattice_jnp": f"{LATTICE}.sublattice"}
SEED = 2**31 + 123


def small_sweep():
    return {**real_config(SWEEP), **SWEEP_SIZE}


def _cell(traffic):
    return Cell(name=f"{SWEEP}.{traffic['engine']}", spec={"chips": 1},
                config=small_sweep(), traffic=traffic, benchmark={})


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = small_root(tmp_path_factory.mktemp("cells"))
    with open(os.path.join(root, "bench", "configs", f"{SWEEP}.json"),
              "w") as f:
        json.dump(small_sweep(), f)
    return root


def test_sweep_trials_match_run_trials_point_by_point():
    """Every trial of the sweep's one batch, against the reference under
    its own point's physics; and trial i of every point starts as trial i
    of a single-point batch does. One chunk program ran all eight
    points."""
    cell = _cell({"engine": "sublattice", "check_sample": 16})
    driver = drivers.of(cell)
    _, got = drivers.run(cell, SEED, 3)
    assert cells.find_module("metrics", "chunk_traces_per_point",
                             cell.root).read(None) == 1 / 8
    ids, ref = driver.reference(cell, SEED, 3)
    assert ids.tolist() == list(range(16))
    assert driver.check(cell, got, (ids, ref)) == {"trials_differing": 0}
    assert np.array_equal(got["final_counts"], ref[:, -1])
    per_point, points = driver.points(cell.config)
    assert per_point == 2 and len(points) == 8
    for q, point in enumerate(points):
        alone = references.trials(point, "sublattice", SEED, [0, 1], 3)
        assert np.array_equal(alone, ref[2 * q:2 * q + 2])
    starts = ref[:, 0].reshape(8, 2, -1)
    assert (starts == starts[:1]).all()
    assert len({ref[2 * q, -1].tobytes() for q in range(8)}) > 1


def test_control_fails_the_sweep():
    cell = _cell({"engine": "sublattice", "check_sample": 16})
    numbers = control_numbers(cell, 2**31 + 13, 4)
    limits = drivers.find("run_sweep").LIMITS
    assert numbers["trials_differing"] > limits["trials_differing"]


def _patch_step(monkeypatch, wrap):
    """Re-register the sublattice engine with its one-MCS step wrapped by
    ``wrap(p, step)``; the step takes the sweep's point where it has one."""
    spec = engines.get_engine("sublattice")

    def build(p, dom):
        built = spec.build(p, dom)
        return built._replace(one_mcs=wrap(p, built.one_mcs))

    monkeypatch.setitem(engines._REGISTRY, "sublattice", engines.EngineSpec(
        name="sublattice", caps=spec.caps, build=build))


def state_unchanged(p, step):
    def one_mcs(grid, key, *point):
        _, kept, att = step(grid, key, *point)
        return grid, kept, att
    return one_mcs


def answer_altered(p, step):
    def one_mcs(grid, key, *point):
        grid, kept, att = step(grid, key, *point)
        return grid.at[0, 0].set(grid[0, 0] % p.species + 1), kept, att
    return one_mcs


@pytest.mark.parametrize("kind", list(CELLS))
def test_sound_run_is_correct(root, kind, capsys):
    rc, result = run_cell(root, CELLS[kind], capsys=capsys)
    assert rc == 0 and result["correct"] is True
    assert result["failed"] == 0
    assert all(c["value"] == 0 for c in result["check"].values())


@pytest.mark.parametrize("kind", list(CELLS))
@pytest.mark.parametrize("fault", [state_unchanged, answer_altered],
                         ids=["state_unchanged", "answer_altered"])
def test_fault_in_the_step_is_caught(root, kind, fault, monkeypatch,
                                     capsys):
    _patch_step(monkeypatch, fault)
    rc, result = run_cell(root, CELLS[kind], capsys=capsys)
    assert rc == 0 and result["correct"] is False
    assert result["failed"] > 0


def test_trials_given_another_points_physics_are_caught(root, monkeypatch,
                                                        capsys):
    """The sweep's trials each run the next point's dominance matrix and
    thresholds: the same lattices and streams, the wrong physics."""
    resolve = scenarios.resolve_points

    def shifted(*a, **kw):
        p, points = resolve(*a, **kw)
        return p, points[1:] + points[:1]

    monkeypatch.setattr(scenarios, "resolve_points", shifted)
    rc, result = run_cell(root, CELLS["sweep"], capsys=capsys)
    assert rc == 0 and result["correct"] is False
    assert result["check"]["trials_differing"]["value"] > 0
