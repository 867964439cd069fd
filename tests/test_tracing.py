"""Chunk records of the drivers (core/tracing.py): one record per consumed
chunk, the exact count of blocking device-to-host reads, span times that
fit inside their chunk, the bound on kept records, and the spans' events
in a profiler trace."""
import glob
import os

import numpy as np
import pytest

from repro.core import (EngineConfig, RunConfig, make_scenario, simulate,
                        tracing)
from repro.core.trials import run_trials

ENGINE = EngineConfig(engine="batched")
OBSERVABLES = ("densities", "interface_length")


def _run(driver, obs, mcs=6, chunk=2, async_stats=True, hooks=()):
    sc = make_scenario("park3")
    run = RunConfig(height=12, length=12, mcs=mcs, chunk_mcs=chunk, seed=3,
                    observables=OBSERVABLES if obs else ())
    if driver == "simulate":
        simulate(sc, engine=ENGINE, run=run, stop_on_stasis=False,
                 hooks=hooks)
    else:
        run_trials(sc, n_trials=3, engine=ENGINE, run=run,
                   stop_on_stasis=False, async_stats=async_stats,
                   hooks=hooks)
    return tracing.last_run()


# blocking reads per chunk on each path: simulate reads its per-MCS rows
# (the observable ring, else the counts), kept and attempts; run_trials
# reads alive masks, the ring when observables are on, final counts, kept
# and attempts
PATHS = [
    pytest.param("simulate", False, True, 3, id="simulate"),
    pytest.param("simulate", True, True, 3, id="simulate-observables"),
    pytest.param("run_trials", False, True, 4, id="run_trials-async"),
    pytest.param("run_trials", False, False, 4, id="run_trials-sync"),
    pytest.param("run_trials", True, True, 5,
                 id="run_trials-observables-async"),
    pytest.param("run_trials", True, False, 5,
                 id="run_trials-observables-sync"),
]


@pytest.mark.parametrize("driver,obs,async_stats,reads", PATHS)
def test_one_record_per_chunk_with_its_exact_reads(driver, obs,
                                                   async_stats, reads):
    run = _run(driver, obs, mcs=7, chunk=2, async_stats=async_stats)
    assert run.driver == driver
    assert [c.mcs for c in run.chunks] == [2, 4, 6, 7]
    assert [c.index for c in run.chunks] == [0, 1, 2, 3]
    assert [c.syncs for c in run.chunks] == [reads] * 4
    assert run.first is run.chunks[0]
    assert run.totals["chunks"] == 4 and run.totals["mcs"] == 7
    assert run.totals["syncs"] == 4 * reads


@pytest.mark.parametrize("driver,obs,async_stats,reads", PATHS)
def test_spans_fit_inside_their_chunk(driver, obs, async_stats, reads):
    run = _run(driver, obs, async_stats=async_stats)
    # records tile the call: each opens as the one before it closes
    starts = [c.start_s for c in run.chunks]
    assert starts == [run.start_s] + [c.end_s for c in run.chunks][:-1]
    for c in run.chunks:
        assert set(c.spans) == set(tracing.SPANS)
        assert all(s >= 0 for s in c.spans.values())
        assert sum(c.spans.values()) <= c.wall_s
    for name in tracing.SPANS:
        assert run.totals[name] == pytest.approx(
            sum(c.spans[name] for c in run.chunks))


def test_hooks_run_inside_the_hooks_span():
    import time

    run = _run("simulate", False,
               hooks=[lambda *_: time.sleep(0.02)])
    assert all(c.spans[tracing.HOOKS] >= 0.02 for c in run.chunks)


def test_records_stay_within_their_bound_over_a_long_run():
    n = tracing.MAX_CHUNKS + 40
    run = _run("simulate", False, mcs=n, chunk=1)
    assert len(run.chunks) == tracing.MAX_CHUNKS
    assert run.chunks[-1].mcs == n and run.chunks[0].mcs == 41
    assert run.first.mcs == 1 and run.first.index == 0
    assert run.totals["chunks"] == n and run.totals["syncs"] == 3 * n


def test_each_call_starts_a_new_record():
    first = _run("simulate", False)
    second = _run("run_trials", False)
    assert tracing.last_run() is second and second is not first
    assert len(first.chunks) == 3 and first.driver == "simulate"


def test_a_call_with_no_mcs_keeps_no_chunk():
    sc = make_scenario("park3")
    run_trials(sc, n_trials=2, engine=ENGINE, n_mcs=0,
               run=RunConfig(height=12, length=12, observables=()))
    run = tracing.last_run()
    assert len(run.chunks) == 0 and run.first is None
    assert run.totals["chunks"] == 0


@pytest.mark.parametrize("driver", ["simulate", "run_trials"])
def test_a_call_traces_its_chunk_program_once(driver):
    run = _run(driver, False, mcs=6, chunk=2)
    assert run.totals["traces"] == 1 and run.totals["points"] == 1


def test_a_shorter_last_chunk_traces_the_program_again():
    run = _run("run_trials", False, mcs=7, chunk=2)
    assert run.totals["traces"] == 2


@pytest.mark.parametrize("n_points", [2, 3])
def test_a_sweep_traces_one_program_for_all_its_points(n_points):
    scs = [make_scenario("park3", mobility=m)
           for m in (3e-5, 3e-4, 3e-3)[:n_points]]
    run_trials(scs, n_trials=3, engine=ENGINE, stop_on_stasis=False,
               run=RunConfig(height=12, length=12, mcs=4, chunk_mcs=2,
                             seed=3, observables=()))
    run = tracing.last_run()
    assert run.totals["traces"] == 1 and run.totals["points"] == n_points
    assert run.totals["chunks"] == 2


def _host_span_events(log_dir):
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in tracing.SPANS:
                    out.setdefault(ev.name, []).append(dict(ev.stats))
    return out


@pytest.mark.parametrize("driver,obs,async_stats,reads", [
    PATHS[1], PATHS[2], PATHS[3]])
def test_spans_land_in_the_profiler_trace_by_name(tmp_path, driver, obs,
                                                  async_stats, reads):
    import jax

    with jax.profiler.trace(str(tmp_path)):
        _run(driver, obs, mcs=6, chunk=2, async_stats=async_stats)
    events = _host_span_events(str(tmp_path))
    assert sorted(events) == sorted(tracing.SPANS)
    # one event per chunk, the index of the record it went to a stat and
    # not a part of the name; with a chunk in flight ahead of the host,
    # the first record holds two dispatches and the last none
    for name in tracing.SPANS:
        got = sorted(s["chunk"] for s in events[name])
        want = ([0, 0, 1] if name == tracing.DISPATCH and driver ==
                "run_trials" and async_stats else [0, 1, 2])
        assert got == want, name


def test_reads_are_host_arrays_of_the_device_values():
    import jax.numpy as jnp

    run = tracing.begin("probe")
    got = run.read(jnp.arange(4))
    assert isinstance(got, np.ndarray) and got.tolist() == [0, 1, 2, 3]
    assert run.current.syncs == 1


@pytest.mark.parametrize("mcs,chunk,want", [
    (6, 2, "first chunk "), (6, 6, "with its compile included")])
def test_cli_rate_reads_the_chunk_records(mcs, chunk, want):
    from repro.launch.escg_run import chunk_rates

    run = _run("simulate", False, mcs=mcs, chunk=chunk)
    line = chunk_rates(12 * 12)
    assert want in line and "updates/s" in line
    if chunk < mcs:     # the rate leaves the first chunk out
        assert f"over {mcs - chunk} MCS" in line
    assert tracing.last_run() is run
