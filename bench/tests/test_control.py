"""The control fails the comparison: the reference computed in bfloat16,
put in the program's place, reads above every limit that it should, on
each cell's path at test size. The same control at the cells' own sizes
runs on the chip through ``bench/control.py``."""
import pytest

from bench import drivers
from bench.cells import Cell
from bench.control import control_numbers

from bench_cases import LATTICE, TRIALS, small_config


@pytest.mark.parametrize("seed", [7, 2**31 + 9])
def test_control_fails_the_trial_batch(seed):
    cell = Cell(name="t", spec={"chips": 1},
                config=small_config(TRIALS, trials=16),
                traffic={"engine": "sublattice", "check_sample": 16},
                benchmark={})
    numbers = control_numbers(cell, seed, 4)
    limits = drivers.find("run_trials").LIMITS
    assert numbers["trials_differing"] > limits["trials_differing"]


@pytest.mark.parametrize("engine", ["pallas_fused", "sublattice"])
def test_control_fails_the_lattice(engine):
    cell = Cell(name="t", spec={"chips": 1},
                config=small_config(LATTICE, height=64, length=128),
                traffic={"engine": engine}, benchmark={})
    numbers = control_numbers(cell, 2**31 + 3, 4)
    limits = drivers.find("simulate").LIMITS
    assert numbers["cells_differing"] > limits["cells_differing"]
    assert numbers["stream_rows_differing"] > \
        limits["stream_rows_differing"]
