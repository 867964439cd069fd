import pytest

from bench import peaks


def test_work_bytes_of_the_3200_lattice():
    cfg = {"height": 3200, "length": 3200, "trials": 1,
           "cell_dtype": "int32"}
    assert peaks.work_bytes_per_mcs(cfg) == 81_920_000


@pytest.mark.parametrize("dtype,trials,want", [
    ("int8", 1, 2 * 200 * 200), ("int32", 2000, 2 * 200 * 200 * 4 * 2000)])
def test_work_bytes_scale_with_cell_width_and_trials(dtype, trials, want):
    cfg = {"height": 200, "length": 200, "trials": trials,
           "cell_dtype": dtype}
    assert peaks.work_bytes_per_mcs(cfg) == want


def test_v5e_hbm_peak():
    assert peaks.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9


def test_unknown_kind_raises():
    with pytest.raises(KeyError, match="TPU v99"):
        peaks.peak("TPU v99", "hbm_bytes_per_s")
