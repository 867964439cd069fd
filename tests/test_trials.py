"""The device-sharded trial subsystem (core/trials.py, DESIGN.md §4).

Fast tests run on the single real CPU device; the device-layout
bit-identity acceptance test spawns a subprocess with fake CPU devices
(slow, nightly CI)."""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (EngineConfig, EscgParams, RunConfig,
                        dominance as dm, make_scenario)
from repro.core.trials import (TrialResult, build_trial_chunk, pad_trials,
                               pod_sharding, run_trials,
                               trial_grids_and_keys)

pytestmark = pytest.mark.composed   # re-run by the CI 8-fake-device job

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def small_params(**kw):
    base = dict(length=12, height=12, species=3, seed=9)
    base.update(kw)
    return EscgParams(**base)


# ------------------------------- driver ----------------------------------- #

def test_run_trials_returns_trial_result():
    r = run_trials(small_params(), dm.RPS(), n_trials=5, n_mcs=10)
    assert isinstance(r, TrialResult)
    assert r.survival.shape == (5, 3) and r.survival.dtype == bool
    assert r.densities.shape == (5, 4)
    np.testing.assert_allclose(r.densities.sum(axis=1), 1.0, atol=1e-6)
    assert r.stasis_mcs.shape == (5,)
    assert r.extinction_mcs.shape == (5, 3)
    assert r.mcs_completed == 10
    assert r.n_trials == 5 and r.n_devices >= 1
    # 10 MCS on a 12x12 RPS grid: everyone still alive, nothing extinct
    assert r.survival.all()
    assert (r.extinction_mcs == -1).all()
    assert 0.0 < r.kept_fraction <= 1.0


def test_trial_prefix_stability():
    """fold-in keys: trial t's trajectory is independent of the batch size,
    so a prefix of a larger batch equals the smaller batch (this is also
    what makes padding sound)."""
    p = small_params(species=5, mobility=1e-4)
    dom = dm.RPSLS()
    r5 = run_trials(p, dom, n_trials=5, n_mcs=8, stop_on_stasis=False)
    r3 = run_trials(p, dom, n_trials=3, n_mcs=8, stop_on_stasis=False)
    np.testing.assert_array_equal(r3.survival, r5.survival[:3])
    np.testing.assert_array_equal(r3.densities, r5.densities[:3])
    np.testing.assert_array_equal(r3.stasis_mcs, r5.stasis_mcs[:3])
    np.testing.assert_array_equal(r3.extinction_mcs, r5.extinction_mcs[:3])


def test_chunking_invariance():
    """Statistics are independent of the chunk split (the per-MCS key
    threading never sees chunk boundaries)."""
    p = small_params(species=5, mobility=1e-4)
    dom = dm.RPSLS()
    r_mono = run_trials(p, dom, 4, n_mcs=9, chunk_mcs=9,
                        stop_on_stasis=False)
    r_chunk = run_trials(p, dom, 4, n_mcs=9, chunk_mcs=2,
                         stop_on_stasis=False)
    np.testing.assert_array_equal(r_mono.survival, r_chunk.survival)
    np.testing.assert_array_equal(r_mono.densities, r_chunk.densities)
    np.testing.assert_array_equal(r_mono.stasis_mcs, r_chunk.stasis_mcs)
    np.testing.assert_array_equal(r_mono.extinction_mcs,
                                  r_chunk.extinction_mcs)


def test_stasis_early_exit_and_recording():
    """Single species + empties: stasis from MCS 1; the driver exits at the
    first chunk boundary instead of running all 500 MCS."""
    p = EscgParams(length=10, height=10, species=1, mcs=500, chunk_mcs=50,
                   empty=0.5, mu=0.0, sigma=1.0, epsilon=0.0, seed=0)
    r = run_trials(p, np.zeros((1, 1), np.float32), n_trials=3)
    assert (r.stasis_mcs == 1).all()
    assert r.mcs_completed == 50          # one chunk, then the early exit


def test_async_stats_schedule_invariance():
    """async_stats keeps one speculative chunk in flight while the host
    folds statistics; the schedule must not leak into ANY result field —
    including mcs_completed at a stasis early-exit, where the in-flight
    chunk is dropped unconsumed."""
    p = small_params(species=5, mobility=1e-4)
    dom = dm.RPSLS()
    a = run_trials(p, dom, 4, n_mcs=9, chunk_mcs=2, stop_on_stasis=False,
                   async_stats=True)
    b = run_trials(p, dom, 4, n_mcs=9, chunk_mcs=2, stop_on_stasis=False,
                   async_stats=False)
    np.testing.assert_array_equal(a.survival, b.survival)
    np.testing.assert_array_equal(a.densities, b.densities)
    np.testing.assert_array_equal(a.stasis_mcs, b.stasis_mcs)
    np.testing.assert_array_equal(a.extinction_mcs, b.extinction_mcs)
    assert a.mcs_completed == b.mcs_completed == 9

    pe = EscgParams(length=10, height=10, species=1, mcs=500, chunk_mcs=50,
                    empty=0.5, mu=0.0, sigma=1.0, epsilon=0.0, seed=0)
    dom1 = np.zeros((1, 1), np.float32)
    for async_stats in (True, False):
        r = run_trials(pe, dom1, n_trials=3, async_stats=async_stats)
        assert r.mcs_completed == 50, async_stats


def test_async_early_exit_drops_speculative_chunk_with_live_dynamics():
    """The sharp edge of the speculative schedule: single species with
    empties reaches stasis at MCS 1 (alive <= 1 forever) while the
    densities KEEP evolving as rare reproduction events fill empties
    (migration-dominated rates keep the fill slow enough not to saturate)
    — so the chunk the async driver has in flight at the early exit
    carries genuinely different statistics. Folding it in would change
    densities and mcs_completed; every field must match the synchronous
    schedule exactly."""
    p = EscgParams(length=12, height=12, species=1, mcs=40, chunk_mcs=4,
                   empty=0.6, mu=0.0, sigma=0.02, epsilon=1.0, seed=2)
    dom = np.zeros((1, 1), np.float32)
    sync = run_trials(p, dom, n_trials=3, async_stats=False)
    # the dynamics are really live past the exit point: four more MCS of
    # the same run change the density stream, so the dropped speculative
    # chunk WOULD have perturbed the stats had it been folded in
    longer = run_trials(p.replace(chunk_mcs=8), dom, n_trials=3,
                        async_stats=False)
    assert not np.array_equal(longer.densities, sync.densities)
    assert longer.mcs_completed == 8

    r = run_trials(p, dom, n_trials=3, async_stats=True)
    assert r.mcs_completed == sync.mcs_completed == 4
    np.testing.assert_array_equal(r.survival, sync.survival)
    np.testing.assert_array_equal(r.densities, sync.densities)
    np.testing.assert_array_equal(r.stasis_mcs, sync.stasis_mcs)
    np.testing.assert_array_equal(r.extinction_mcs, sync.extinction_mcs)
    assert (r.stasis_mcs == 1).all()


def test_cell_dtype_honoured_and_value_stable():
    """The trial driver honours params.cell_dtype (the legacy vmap runner
    dropped it), and the dtype does not change trajectories."""
    p8 = small_params(cell_dtype="int8")
    grids, _ = trial_grids_and_keys(p8.validate(), jax.random.PRNGKey(0), 2)
    assert grids.dtype == jnp.int8
    r8 = run_trials(p8, dm.RPS(), 3, n_mcs=6, stop_on_stasis=False)
    r32 = run_trials(small_params(cell_dtype="int32"), dm.RPS(), 3, n_mcs=6,
                     stop_on_stasis=False)
    np.testing.assert_array_equal(r8.survival, r32.survival)
    np.testing.assert_array_equal(r8.densities, r32.densities)


def test_zero_mcs_returns_initial_state():
    """n_mcs=0 (Park Table 4.2 has MCS=0 cells): no chunks run and the
    result carries the initial survival mask, like the legacy runner."""
    r = run_trials(small_params(empty=0.0), dm.RPS(), 3, n_mcs=0)
    assert r.mcs_completed == 0
    assert r.survival.all()
    np.testing.assert_allclose(r.densities.sum(axis=1), 1.0, atol=1e-6)
    assert r.kept_fraction == 1.0
    with pytest.raises(ValueError, match="chunk_mcs"):
        run_trials(small_params(), dm.RPS(), 3, n_mcs=5, chunk_mcs=0)


def test_padding_helper():
    assert pad_trials(5, 4) == 8
    assert pad_trials(8, 4) == 8
    assert pad_trials(1, 4) == 4
    assert pad_trials(7, 1) == 7


def test_pod_sharding_validation():
    with pytest.raises(ValueError, match="trial_devices"):
        pod_sharding(0)
    with pytest.raises(ValueError, match="local devices"):
        pod_sharding(10_000)


def test_rejects_non_vmappable_engine():
    with pytest.raises(ValueError, match="vmappable"):
        run_trials(EscgParams(length=16, height=16, engine="sharded",
                              tile=(8, 8)), dm.RPS(), n_trials=2, n_mcs=1)


def test_hooks_stream_per_chunk():
    calls = []
    run_trials(small_params(), dm.RPS(), 4, n_mcs=9, chunk_mcs=3,
               stop_on_stasis=False,
               hooks=[lambda m, alive: calls.append((m, alive.shape))])
    assert [c[0] for c in calls] == [3, 6, 9]
    assert all(c[1] == (4,) for c in calls)


def test_trial_chunk_shapes():
    p = small_params().validate()
    dom = jnp.asarray(dm.RPS(), jnp.float32)
    grids, keys = trial_grids_and_keys(p, jax.random.PRNGKey(1), 4)
    chunk = build_trial_chunk(p, dom)
    g2, k2, cnts, alive, kept, att = chunk(grids, keys, 5)
    assert g2.shape == (4, 12, 12)
    assert cnts.shape == (4, 4)
    assert alive.shape == (4, 5, 3) and alive.dtype == jnp.bool_
    assert kept.shape == (4,) and att.shape == (4,)
    assert int(cnts.sum()) == 4 * p.n_cells


# ----------------------- TrialResult statistics --------------------------- #

def test_trial_result_statistics_roundtrip():
    surv = np.array([[True, True, False],
                     [True, False, False],
                     [True, True, True],
                     [True, False, False]])
    res = TrialResult(
        survival=surv,
        densities=np.array([[0.0, 0.5, 0.5, 0.0]] * 4),
        stasis_mcs=np.array([3, -1, 7, 2]),
        extinction_mcs=np.array([[-1, -1, 4]] * 4),
        mcs_completed=10, kept_fraction=0.9, n_trials=4, n_devices=2)

    np.testing.assert_allclose(res.survival_probabilities(),
                               [1.0, 0.5, 0.25])
    hist = res.survivors_hist()
    assert hist.shape == (4,)
    np.testing.assert_allclose(hist, [0.0, 0.5, 0.25, 0.25])
    assert abs(hist.sum() - 1.0) < 1e-9
    assert res.extinction_probability(1) == 0.0
    assert res.extinction_probability(3) == 0.75
    assert res.species == 3

    back = TrialResult.from_json(res.to_json())
    np.testing.assert_array_equal(back.survival, res.survival)
    np.testing.assert_allclose(back.densities, res.densities)
    np.testing.assert_array_equal(back.stasis_mcs, res.stasis_mcs)
    np.testing.assert_array_equal(back.extinction_mcs, res.extinction_mcs)
    assert back.mcs_completed == res.mcs_completed
    assert back.kept_fraction == res.kept_fraction
    assert back.n_trials == res.n_trials
    assert back.n_devices == res.n_devices
    assert back.survival.dtype == bool


def test_legacy_wrapper_returns_survival_mask():
    from repro.core import run_trials as legacy
    surv = legacy(small_params(), dm.RPS(), 5, n_mcs=10)
    assert isinstance(surv, np.ndarray)
    assert surv.shape == (5, 3) and surv.dtype == bool


# ------------------------------- sweeps ------------------------------------ #
# A sweep runs P scenarios of one shape as one batch, each trial with its
# point's dominance matrix and thresholds as engine operands; trial i of
# every point keeps the key of a single-point call.

SWEEP_RUN = RunConfig(height=24, length=24, mcs=3, chunk_mcs=1,
                      seed=2**31 + 11)
SWEEP_TILE = (8, 8)
ALPHA_BETA = [(0.15, 0.75), (0.6, 0.0), (0.6, 0.5)]
MOBILITIES = [0.0, 3e-4, 3e-3]


POINTS = {
    "alpha_beta": lambda: [make_scenario("probabilistic", alpha=a, beta=b)
                           for a, b in ALPHA_BETA],
    "mobility": lambda: [make_scenario("park3", mobility=m)
                         for m in MOBILITIES],
}


@functools.lru_cache(maxsize=None)
def _streamed(points, engine, q=None, n=4):
    """run_trials over the sweep ``points`` (point ``q`` alone, if given)
    with the alive counts each chunk streams to the hook, stacked on a
    last axis. Kept for the module: each call compiles a program."""
    scenarios = POINTS[points]()
    seen = []
    res = run_trials(scenarios if q is None else scenarios[q], n_trials=n,
                     engine=EngineConfig(engine=engine, tile=SWEEP_TILE),
                     run=SWEEP_RUN, stop_on_stasis=False,
                     hooks=[lambda done, alive: seen.append(np.array(alive))])
    return res, np.stack(seen, axis=-1)


def _assert_same_trials(a, b):
    for f in ("survival", "densities", "stasis_mcs", "extinction_mcs"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))
    assert (a.mcs_completed, a.kept_fraction, a.n_trials) == \
        (b.mcs_completed, b.kept_fraction, b.n_trials)
    assert a.observables.keys() == b.observables.keys()
    for k in a.observables:
        np.testing.assert_array_equal(a.observables[k], b.observables[k])


@pytest.mark.parametrize("engine,points", [
    ("sublattice", "alpha_beta"), ("reference", "alpha_beta"),
    ("batched", "alpha_beta"), ("sublattice", "mobility")])
def test_sweep_equals_single_point_calls(engine, points):
    """One batch of 3 points x 4 trials is three single-point calls, bit
    for bit: counts, alive streams, stasis, extinction and survival. The
    (alpha, beta) sweep moves the dominance matrix; the park3 sweep over
    mobility moves the threshold operands, and streams observables."""
    sweep, alive = _streamed(points, engine)
    assert isinstance(sweep, list) and len(sweep) == 3
    alive = alive.reshape(3, 4, -1)
    for q in range(3):
        single, alive_q = _streamed(points, engine, q)
        assert isinstance(single, TrialResult)
        _assert_same_trials(sweep[q], single)
        np.testing.assert_array_equal(alive[q], alive_q)
    # each trial played its own point: the same lattices part ways
    assert any(not np.array_equal(sweep[0].densities, r.densities)
               for r in sweep[1:])


def _reference_config(sc):
    """A scenario as the plain reference's configuration: its physics
    stated in full, dominance edges included."""
    d = sc.dominance()
    return {"species": sc.species, "neighbourhood": sc.neighbourhood,
            "height": SWEEP_RUN.height, "length": SWEEP_RUN.length,
            "empty": sc.empty, "tile": list(SWEEP_TILE),
            "mobility": sc.mobility, "epsilon": sc.epsilon, "mu": sc.mu,
            "sigma": sc.sigma,
            "dominance": [[int(w), int(l), float(d[w, l])]
                          for w, l in zip(*np.nonzero(d))]}


@pytest.mark.parametrize("points", ["alpha_beta"])
def test_sweep_points_follow_the_plain_reference(points):
    """Each point's trials equal the benchmark's plain reference of the
    sublattice engine (bench/references), which shares no code with the
    program, followed under that point's own physics."""
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from bench import references

    sweep, _ = _streamed(points, "sublattice")
    n_cells = SWEEP_RUN.height * SWEEP_RUN.length
    for q, sc in enumerate(POINTS[points]()):
        counts = references.trials(_reference_config(sc), "sublattice",
                                   SWEEP_RUN.seed, np.arange(4),
                                   SWEEP_RUN.mcs)
        np.testing.assert_array_equal(
            np.rint(sweep[q].densities * n_cells).astype(np.int64),
            counts[:, -1])
        np.testing.assert_array_equal(sweep[q].survival,
                                      counts[:, -1, 1:] > 0)


@pytest.mark.parametrize("other,field", [
    (dict(empty=0.1), "empty"),
    (dict(neighbourhood=8), "neighbourhood"),
    (dict(boundary="reflect"), "flux"),
])
def test_sweep_points_must_share_their_shape(other, field):
    scs = [make_scenario("park3"), make_scenario("park3", **other)]
    with pytest.raises(ValueError, match=f"differ in '{field}'"):
        run_trials(scs, n_trials=2, engine=EngineConfig(engine="batched"),
                   run=SWEEP_RUN)


def test_sweep_points_must_share_their_species():
    scs = [make_scenario("park3"), make_scenario("nspecies5")]
    with pytest.raises(ValueError, match="differ in 'species'"):
        run_trials(scs, n_trials=2, engine=EngineConfig(engine="batched"),
                   run=SWEEP_RUN)


@pytest.mark.parametrize("engine", ["pallas", "pallas_fused"])
def test_engine_with_compiled_rates_refuses_two_points(engine):
    with pytest.raises(ValueError, match="physics_operand"):
        run_trials(POINTS["mobility"]()[:2], n_trials=2,
                   engine=EngineConfig(engine=engine, tile=SWEEP_TILE),
                   run=SWEEP_RUN)


def test_park_survival_sweep_equals_its_points():
    from repro.core import park

    key = jax.random.PRNGKey(5)
    kw = dict(L=12, n_trials=3, mcs=4, key=key)
    swept = park.survival_probabilities_sweep(ALPHA_BETA[:2], **kw)
    for (a, b), (ps, hist) in zip(ALPHA_BETA[:2], swept):
        ps1, hist1 = park.survival_probabilities(a, b, **kw)
        np.testing.assert_array_equal(ps, ps1)
        np.testing.assert_array_equal(hist, hist1)


# ------------------------------ multi-device ------------------------------- #

@pytest.mark.slow
def test_trials_bit_identical_across_device_layouts(subproc):
    """Acceptance: the sharded trial runner is bit-identical to the
    single-device vmap path for pod widths 1/2/4, including a trial count
    that does not divide the device count (6 pads to 8 on 4 devices)."""
    out = subproc("""
        import numpy as np
        from repro.core import EscgParams, dominance as dm
        from repro.core.trials import run_trials
        p = EscgParams(length=16, height=16, species=5, mobility=1e-4,
                       seed=3, cell_dtype='int8')
        dom = dm.RPSLS()
        rs = {d: run_trials(p, dom, n_trials=6, n_mcs=8, trial_devices=d,
                            chunk_mcs=3, stop_on_stasis=False)
              for d in (1, 2, 4)}
        base = rs[1]
        for d in (2, 4):
            r = rs[d]
            assert r.n_devices == d
            assert np.array_equal(r.survival, base.survival), d
            assert np.array_equal(r.densities, base.densities), d
            assert np.array_equal(r.stasis_mcs, base.stasis_mcs), d
            assert np.array_equal(r.extinction_mcs,
                                  base.extinction_mcs), d
        print("POD_BIT_IDENTICAL")
    """, n_devices=4)
    assert "POD_BIT_IDENTICAL" in out


@pytest.mark.slow
def test_trials_default_pod_width_uses_all_devices(subproc):
    """trial_devices=None shards over every local device and still matches
    the explicit single-device run."""
    out = subproc("""
        import numpy as np
        from repro.core import EscgParams, dominance as dm
        from repro.core.trials import run_trials
        p = EscgParams(length=12, height=12, species=3, seed=0)
        r_all = run_trials(p, dm.RPS(), 5, n_mcs=4, stop_on_stasis=False)
        r_one = run_trials(p, dm.RPS(), 5, n_mcs=4, trial_devices=1,
                           stop_on_stasis=False)
        assert r_all.n_devices == 4
        assert np.array_equal(r_all.survival, r_one.survival)
        assert np.array_equal(r_all.densities, r_one.densities)
        print("POD_DEFAULT_OK")
    """, n_devices=4)
    assert "POD_DEFAULT_OK" in out
