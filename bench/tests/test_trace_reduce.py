"""The trace reduction: interval arithmetic on hand-made intervals, and
the whole reduction on small traces recorded on a TPU v5e chip and kept
under ``bench/tests/data``."""
import glob
import os

import pytest

from bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")


def test_union_merges_overlaps_and_keeps_gaps():
    got = tr._union([(5, 7), (0, 2), (1, 3), (3, 4), (10, 11)])
    assert got == [(0, 4), (5, 7), (10, 11)]


def test_nested_ops_count_once():
    events = [(0, 10, "while.1"), (1, 4, "fusion.2"), (4, 6, "fusion.3"),
              (6, 9, "custom-call.4"), (12, 15, "copy.5")]
    assert tr.self_times(events) == [2, 3, 2, 3, 3]


def test_op_names_drop_the_instruction_text():
    assert tr.op_name("%while.13 = (s32[]) while(%t), body=%b") == "while.13"
    assert tr.op_name("fusion.2") == "fusion.2"


def test_gaps_go_to_the_host_event_covering_most_of_them():
    gaps = [(0, 100_000), (200_000, 300_000), (400_000, 400_010)]
    host = [(0, 1_000_000, tr.WINDOW_SPAN),        # the window never labels
            (-50, 90_000, "PjitFunction(chunk)"),
            (50_000, 99_000, "bench.hook"),
            (150_000, 350_000, "np.asarray")]
    by = tr._label_gaps(gaps, host, window_lo=0)
    assert by["PjitFunction(chunk)"] == 100_000
    assert by["np.asarray"] == 100_000
    assert by[tr.SHORT_GAPS] == 10


def test_kernels_are_told_by_custom_call():
    assert tr.is_kernel("fusion.1", {"hlo_category": "custom-call"})
    assert not tr.is_kernel("fusion.1", {"hlo_category": "loop fusion"})
    assert tr.is_kernel("custom-call.3", {})
    assert not tr.is_kernel("add.3", {"long_name": "%add.3 = s32[] add"})


RECORDED = sorted(glob.glob(os.path.join(DATA, "*.xplane.pb")))


@pytest.mark.parametrize("path", RECORDED,
                         ids=[os.path.basename(p) for p in RECORDED])
def test_recorded_trace_reduces_as_when_recorded(path):
    import json

    got = tr.summarize(path)
    with open(path.replace(".xplane.pb", ".summary.json")) as f:
        want = json.load(f)
    assert got.window_ns == want["window_ns"]
    assert got.busy_ns == want["busy_ns"]
    assert got.kernel_ns == want["kernel_ns"]
    assert got.xla_ns == want["xla_ns"]
    assert [list(x) for x in got.top_ops] == want["top_ops"]
    assert [list(x) for x in got.idle_gaps] == want["idle_gaps"]
    assert 0 < got.busy_ns <= got.window_ns
    assert 0 <= got.idle_share < 1
    assert got.n_devices == 1


def test_recorded_traces_tell_kernels_from_xla_ops():
    by_cell = {os.path.basename(p).split(".")[0]: tr.summarize(p)
               for p in RECORDED}
    assert set(by_cell) == {"probabilistic_L200_x2000", "park3_L3200"}
    # the jnp engine runs no Pallas kernel; the fused engine's round is one
    assert by_cell["probabilistic_L200_x2000"].kernel_ns == 0
    assert by_cell["park3_L3200"].kernel_ns > 0


@pytest.mark.parametrize("path", RECORDED,
                         ids=[os.path.basename(p) for p in RECORDED])
def test_describe_lists_the_device_ops(path):
    text = tr.describe(path, limit=2)
    assert "PLANE /device:" in text and f"LINE {tr.OPS_LINE!r}" in text
