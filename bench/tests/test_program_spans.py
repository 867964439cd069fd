"""The readers of the program's chunk records (``bench/program_spans.py``
and the four metrics on it), after a run of each small cell through
``bench.drivers.run`` on the CPU."""
import math
import sys
from types import SimpleNamespace

import pytest

from bench import cells, drivers, harness

from bench_cases import LATTICE, TRIALS, small_root

READERS = ("host_wait_share", "chunk_readback_s", "chunk_host_s",
           "syncs_per_chunk")
# blocking reads per chunk: the trial batch (no streamed observables)
# reads alive masks, final counts, kept and attempts; the lattice reads
# its observable ring, kept and attempts
CELLS = {f"{TRIALS}.sublattice": 4, f"{LATTICE}.pallas_fused": 3}
WINDOW_CHUNKS = 2


@pytest.fixture(scope="module", params=sorted(CELLS))
def ran(request, tmp_path_factory):
    """One warm-up chunk and a window through the cell's driver; the
    context a ``--trace 1`` run hands the readers."""
    cell = cells.load(request.param, small_root(tmp_path_factory.mktemp(
        "root")))
    n_mcs = (1 + WINDOW_CHUNKS) * cell.chunk_mcs
    boundaries, _ = drivers.run(cell, 2**31 + 11, n_mcs)
    window_s = boundaries[-1] - boundaries[0]
    ctx = SimpleNamespace(
        trace=SimpleNamespace(window_ns=window_s * 1e9), config=cell.config,
        window_mcs=WINDOW_CHUNKS * cell.chunk_mcs)
    return request.param, ctx


def _read(name, ctx):
    return harness._reader(name, cells.DEFAULT_ROOT)(ctx)


@pytest.mark.parametrize("name", READERS)
def test_reader_is_finite_after_a_run(ran, name):
    _, ctx = ran
    value = _read(name, ctx)
    assert value is not None and math.isfinite(value) and value >= 0


def test_host_wait_share_is_a_share_of_the_window(ran):
    _, ctx = ran
    assert 0 < _read("host_wait_share", ctx) < 1


def test_syncs_per_chunk_is_the_exact_read_count(ran):
    cell, ctx = ran
    assert _read("syncs_per_chunk", ctx) == CELLS[cell]


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_nothing_from_a_record_shorter_than_the_window(
        ran, name):
    _, ctx = ran
    longer = SimpleNamespace(**{**vars(ctx), "window_mcs": 10**6})
    assert _read(name, longer) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_nothing_from_an_empty_record(ran, name,
                                                   monkeypatch):
    from repro.core import tracing

    _, ctx = ran
    monkeypatch.setattr(tracing, "_last", None)
    assert _read(name, ctx) is None
    tracing.begin("simulate")
    assert _read(name, ctx) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_nothing_from_a_program_without_records(
        ran, name, monkeypatch):
    import repro.core

    _, ctx = ran
    monkeypatch.delattr(repro.core, "tracing")
    monkeypatch.setitem(sys.modules, "repro.core.tracing", None)
    assert _read(name, ctx) is None
