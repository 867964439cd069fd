"""The plain references against the engines they stand for, at small
sizes on the CPU: bit for bit, through the drivers' own entry points."""
import numpy as np
import pytest

from bench import compare, drivers, references
from bench.cells import Cell
from bench.references import pallas_fused

from bench_cases import LATTICE, TRIALS, small_config

SEED = 2**31 + 123

# Random123 known-answer vectors of Philox-4x32-10
KAT = [
    ((0, 0, 0, 0), (0, 0),
     (0x6627E8D5, 0xE169C58D, 0xBC57AC4C, 0x9B00DBD8)),
    ((0xFFFFFFFF,) * 4, (0xFFFFFFFF,) * 2,
     (0x408F276D, 0x41C83B0E, 0xA20BC7C6, 0x6D5451FD)),
    ((0x243F6A88, 0x85A308D3, 0x13198A2E, 0x03707344),
     (0xA4093822, 0x299F31D0),
     (0xD16CFE09, 0x94FDCCEB, 0x5001E420, 0x24126EA1)),
]


@pytest.mark.parametrize("ctr,key,want", KAT)
def test_philox_known_answers(ctr, key, want):
    got = pallas_fused.philox4x32(*(np.uint32(c) for c in ctr), *key)
    assert tuple(int(x) for x in got) == want


def _cell(name, traffic, **over):
    cfg = small_config(name, **over)
    return Cell(name=f"{name}.{traffic['engine']}", spec={"chips": 1},
                config=cfg, traffic=traffic, benchmark={})


def test_sublattice_trials_match_run_trials():
    cell = _cell(TRIALS, {"engine": "sublattice", "check_sample": 8})
    _, got = drivers.run(cell, SEED, 3)
    ids = np.arange(cell.config["trials"])
    ref = references.trials(cell.config, "sublattice", SEED, ids, 3)
    assert compare.check_trials(got, ref, ids, 1) == {"trials_differing": 0}
    assert np.array_equal(got["final_counts"], ref[:, -1])


def test_fused_lattice_matches_simulate():
    cell = _cell(LATTICE, {"engine": "pallas_fused"})
    _, got = drivers.run(cell, SEED, 2)
    counts, bonds, grid = references.single(cell.config, "pallas_fused",
                                            SEED, 2)
    assert np.array_equal(got["grid"], grid)
    observables = cell.config["observables"]
    assert "interface_length" in observables
    assert compare.check_single(got, counts, bonds, grid, observables) == {
        "stream_rows_differing": 0, "cells_differing": 0}


def test_an_altered_interface_row_is_counted():
    # 40 x 64: 2 N is no power of two, so the share is rounded
    cell = _cell(LATTICE, {"engine": "pallas_fused"}, height=40)
    _, got = drivers.run(cell, SEED, 3)
    counts, bonds, grid = references.single(cell.config, "pallas_fused",
                                            SEED, 3)
    observables = cell.config["observables"]
    assert compare.check_single(got, counts, bonds, grid, observables) == {
        "stream_rows_differing": 0, "cells_differing": 0}
    got["interface_length"] = got["interface_length"].copy()
    got["interface_length"][1, 0] += 1.0 / (2 * grid.size)  # one bond more
    assert compare.check_single(got, counts, bonds, grid,
                                cell.config["observables"]) == {
        "stream_rows_differing": 1, "cells_differing": 0}
    got["interface_length"] = None
    assert compare.check_single(got, counts, bonds, grid,
                                cell.config["observables"])[
        "stream_rows_differing"] == 3


def test_sublattice_single_lattice_matches_simulate():
    """The sublattice streams also drive one lattice, as a later cell of
    the jnp engine at 3200x3200 would."""
    cell = _cell(LATTICE, {"engine": "sublattice"})
    _, got = drivers.run(cell, SEED, 2)
    counts, bonds, grid = references.single(cell.config, "sublattice",
                                            SEED, 2)
    assert np.array_equal(got["grid"], grid)
    assert np.array_equal(got["counts"], counts[1:])
    assert np.array_equal(
        compare.interface_bonds(got["interface_length"], grid.size),
        bonds[:, None])


def test_trial_answers_from_counts():
    # two trials, three steps, species 1..2
    ref = np.array([
        [[0, 5, 5], [0, 6, 4], [0, 10, 0], [0, 10, 0]],
        [[0, 0, 10], [0, 0, 10], [1, 0, 9], [2, 0, 8]],
    ])
    a = compare.trial_answers(ref, chunk_mcs=1)
    assert a["extinction_mcs"].tolist() == [[-1, 2], [0, -1]]
    assert a["stasis_mcs"].tolist() == [2, 1]
    assert a["alive"].tolist() == [[2, 1, 1], [1, 1, 1]]
    assert a["survival"].tolist() == [[True, False], [False, True]]
