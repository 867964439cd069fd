"""The sharded (grid-axis) and sharded_pod (composed) engines — ONE
module, parametrized over the in-region tile-sweep implementation
(``local_kernel``: jnp vs pallas; the two paths are bit-identical by
contract). Merges the former tests/test_sharded.py ESCG tests.

Single-device tests run on the real CPU device (a 1x1 lattice mesh);
multi-device tests spawn subprocesses with fake CPU devices (see
conftest). LM-scaffold multi-device tests live in
tests/test_parallel_scaffold.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:   # hermetic container: deterministic fallback sampler
    from _propcheck import given, settings, strategies as st

from repro.core import EscgParams, dominance as dm, engines, simulate
from repro.core.lattice import init_grid

pytestmark = pytest.mark.composed   # re-run by the CI 8-fake-device job

LOCAL_KERNELS = ("jnp", "pallas")


# --------------------- N=1 shard == sublattice engine --------------------- #

@given(seed=st.integers(0, 10_000), species=st.integers(2, 6),
       cfg=st.sampled_from([(16, 32, 8, 16), (24, 24, 8, 8),
                            (16, 16, 4, 8)]),
       nbhd=st.sampled_from([4, 8]),
       local_kernel=st.sampled_from(LOCAL_KERNELS))
@settings(max_examples=10, deadline=None)
def test_sharded_single_shard_bit_identical_to_sublattice(seed, species,
                                                          cfg, nbhd,
                                                          local_kernel):
    """A sharded run with one shard is bit-identical to the sublattice
    engine — for BOTH tile-sweep implementations: same per-tile Philox
    streams, same shifted-window sweeps."""
    h, w, th, tw = cfg
    kw = dict(length=w, height=h, species=species, neighbourhood=nbhd,
              tile=(th, tw), seed=seed, mobility=1e-3, empty=0.1)
    dom = dm.circulant(species, (1, 2) if species >= 5 else (1,))
    dom_j = jnp.asarray(dom, jnp.float32)

    sub = engines.build(EscgParams(engine="sublattice", **kw), dom_j)
    shd = engines.build(EscgParams(engine="sharded", shard_grid=(1, 1),
                                   local_kernel=local_kernel, **kw), dom_j)
    key = jax.random.PRNGKey(seed)
    key, k0 = jax.random.split(key)
    g_sub = init_grid(k0, h, w, species, 0.1)
    g_shd = jax.device_put(g_sub, shd.grid_sharding)
    for _ in range(3):
        key, k = jax.random.split(key)
        g_sub, kept_a, att_a = sub.one_mcs(g_sub, k)
        g_shd, kept_b, att_b = shd.one_mcs(g_shd, k)
        assert int(att_a) == int(att_b)
    assert jnp.array_equal(g_sub, g_shd)


@pytest.mark.parametrize("local_kernel", LOCAL_KERNELS)
def test_sharded_through_simulate_single_device(local_kernel):
    """Full driver path: engine='sharded' on one device tracks
    engine='sublattice' exactly (grids, densities, stasis accounting)."""
    kw = dict(length=32, height=16, species=3, mcs=6, chunk_mcs=3,
              tile=(8, 8), seed=0, mobility=1e-3, empty=0.1)
    r1 = simulate(EscgParams(engine="sublattice", **kw),
                  stop_on_stasis=False)
    r2 = simulate(EscgParams(engine="sharded", local_kernel=local_kernel,
                             **kw), stop_on_stasis=False)
    np.testing.assert_array_equal(r1.grid, r2.grid)
    np.testing.assert_allclose(r1.densities, r2.densities, atol=0)
    assert r1.mcs_completed == r2.mcs_completed


@pytest.mark.parametrize("local_kernel", LOCAL_KERNELS)
def test_sharded_pod_through_trials_single_device(local_kernel):
    """Composed-engine driver path on one device: run_trials with a
    (1,1,1) mesh tracks the vmapped sublattice trial batch exactly."""
    from repro.core.trials import run_trials
    kw = dict(length=16, height=16, species=5, mobility=1e-3, tile=(8, 8),
              empty=0.1, seed=4)
    dom = dm.RPSLS()
    base = run_trials(EscgParams(engine="sublattice", **kw), dom, 3,
                      n_mcs=4, stop_on_stasis=False)
    r = run_trials(EscgParams(engine="sharded_pod", mesh_shape=(1, 1, 1),
                              local_kernel=local_kernel, **kw), dom, 3,
                   n_mcs=4, stop_on_stasis=False)
    np.testing.assert_array_equal(r.survival, base.survival)
    np.testing.assert_array_equal(r.densities, base.densities)
    np.testing.assert_array_equal(r.stasis_mcs, base.stasis_mcs)
    np.testing.assert_array_equal(r.extinction_mcs, base.extinction_mcs)


# ------------------------- capability validation --------------------------- #

def test_sharded_rejects_infeasible_grid():
    p = EscgParams(length=32, height=16, engine="sharded", tile=(8, 8),
                   shard_grid=(3, 1))   # 3 does not divide 16
    with pytest.raises(ValueError):
        engines.build(p, jnp.asarray(dm.RPS()))


def test_run_trials_rejects_sharded():
    from repro.core import run_trials
    with pytest.raises(ValueError, match="vmappable"):
        run_trials(EscgParams(length=16, height=16, engine="sharded",
                              tile=(8, 8)), dm.RPS(), n_trials=2, n_mcs=1)


def test_mesh_shape_legality_is_registry_driven():
    """EngineCaps.mesh_axes (not the drivers) decide which layouts are
    legal: mesh_shape on a non-composable engine, wrong rank, and bad dims
    all fail at params validation."""
    with pytest.raises(ValueError, match="pod-composable"):
        EscgParams(engine="sublattice", tile=(8, 8), length=16, height=16,
                   mesh_shape=(1, 1, 1)).validate()
    with pytest.raises(ValueError, match="pod-composable"):
        EscgParams(engine="sharded", tile=(8, 8), length=16, height=16,
                   mesh_shape=(1, 1, 1)).validate()
    with pytest.raises(ValueError, match="dims must be >= 1"):
        EscgParams(engine="sharded_pod", tile=(8, 8), length=16, height=16,
                   mesh_shape=(0, 1, 1)).validate()
    # legal on the composed engine
    EscgParams(engine="sharded_pod", tile=(8, 8), length=16, height=16,
               mesh_shape=(1, 1, 1)).validate()


def test_local_kernel_validation():
    with pytest.raises(ValueError, match="local_kernel"):
        EscgParams(engine="sharded", tile=(8, 8), length=16, height=16,
                   local_kernel="cuda").validate()
    # engines that declare supported kernels accept exactly those
    for lk in ("pallas", "fused"):
        EscgParams(engine="sharded", tile=(8, 8), length=16, height=16,
                   local_kernel=lk).validate()
    # engines that don't consume the knob ignore it (same rule as tile)
    EscgParams(engine="batched", local_kernel="pallas").validate()


# --------------- fused local kernel: the second oracle family -------------- #
# jnp/pallas local kernels answer to `sublattice` (the tests above); the
# fused kernel derives proposals in-kernel from Philox counters and answers
# to `pallas_fused` instead (EngineCaps.equiv_oracles, DESIGN.md §6).

def test_sharded_fused_tracks_pallas_fused():
    """engine='sharded', local_kernel='fused' on a 1x1 mesh follows the
    single-device pallas_fused engine bit-for-bit through simulate."""
    kw = dict(length=32, height=16, species=3, mcs=6, chunk_mcs=3,
              tile=(8, 8), seed=0, mobility=1e-3, empty=0.1)
    r1 = simulate(EscgParams(engine="pallas_fused", **kw),
                  stop_on_stasis=False)
    r2 = simulate(EscgParams(engine="sharded", local_kernel="fused", **kw),
                  stop_on_stasis=False)
    np.testing.assert_array_equal(r1.grid, r2.grid)
    np.testing.assert_allclose(r1.densities, r2.densities, atol=0)
    assert r1.mcs_completed == r2.mcs_completed


def test_sharded_pod_fused_through_trials():
    """Composed-engine driver path: run_trials with a (1,1,1) mesh and
    local_kernel='fused' tracks the vmapped pallas_fused batch exactly."""
    from repro.core.trials import run_trials
    kw = dict(length=16, height=16, species=5, mobility=1e-3, tile=(8, 8),
              empty=0.1, seed=4)
    dom = dm.RPSLS()
    base = run_trials(EscgParams(engine="pallas_fused", **kw), dom, 3,
                      n_mcs=4, stop_on_stasis=False)
    r = run_trials(EscgParams(engine="sharded_pod", mesh_shape=(1, 1, 1),
                              local_kernel="fused", **kw), dom, 3,
                   n_mcs=4, stop_on_stasis=False)
    np.testing.assert_array_equal(r.survival, base.survival)
    np.testing.assert_array_equal(r.densities, base.densities)
    np.testing.assert_array_equal(r.stasis_mcs, base.stasis_mcs)
    np.testing.assert_array_equal(r.extinction_mcs, base.extinction_mcs)


def test_sharded_pod_rejects_trial_devices():
    from repro.core.trials import run_trials
    with pytest.raises(ValueError, match="mesh_shape"):
        run_trials(EscgParams(engine="sharded_pod", tile=(8, 8), length=16,
                              height=16), dm.RPS(), n_trials=2, n_mcs=1,
                   trial_devices=2)


def test_mesh_shape_needs_enough_devices():
    p = EscgParams(engine="sharded_pod", tile=(8, 8), length=16, height=16,
                   mesh_shape=(64, 1, 1))
    with pytest.raises(ValueError, match="devices"):
        engines.build(p, jnp.asarray(dm.RPS()))


def test_make_composed_mesh_axes():
    """launch.mesh builds the same ('pod','rows','cols') layout the
    sharded_pod engine uses, with or without lattice validation."""
    from repro.launch.mesh import make_composed_mesh
    m = make_composed_mesh((1, 1, 1))
    assert m.axis_names == ("pod", "rows", "cols")
    m2 = make_composed_mesh((1, 1, 1), height=16, width=16, tile=(8, 8))
    assert (m2.shape["pod"], m2.shape["rows"], m2.shape["cols"]) == (1, 1, 1)
    # rejected either for the device budget (1 device) or, with enough
    # devices, because cols=2 cannot split width 16 into 16-wide tiles
    with pytest.raises(ValueError):
        make_composed_mesh((1, 1, 2), height=16, width=16, tile=(8, 16))


def test_mesh_shape_cli_parser():
    from repro.core.params import _mesh_shape
    assert _mesh_shape("2,2,2") == (2, 2, 2)
    assert _mesh_shape("4x1x2") == (4, 1, 2)
    import argparse
    with pytest.raises(argparse.ArgumentTypeError):
        _mesh_shape("2,2")


# ----------------------------- multi-device ------------------------------- #

@pytest.mark.slow
@pytest.mark.parametrize("local_kernel", LOCAL_KERNELS)
def test_sharded_escg_equals_single_device(subproc, local_kernel):
    """The shard_map spatial decomposition is bit-identical to the
    single-device sublattice engine on a 4x4 device mesh, with externally
    supplied proposals, for both tile-sweep implementations."""
    out = subproc(f"""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import dominance as dm
        from repro.core.lattice import init_grid
        from repro.core.rng import tile_proposal_batch, round_shift
        from repro.core.sharded import sharded_run_round
        from repro.core.sublattice import run_round
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((4, 4), ("data", "model"))
        h, w, th, tw = 32, 64, 8, 16
        key = jax.random.PRNGKey(0)
        grid = init_grid(key, h, w, 5, 0.1)
        dom = jnp.asarray(dm.RPSLS())
        nt = (h // th) * (w // tw)
        for r in range(3):
            kp, ks, key = jax.random.split(key, 3)
            props = tile_proposal_batch(kp, nt, 61, (th-2)*(tw-2), 4)
            shift = round_shift(ks, th, tw)
            a = run_round(grid, props, shift, (th, tw), 0.3, 0.6, dom)
            b = sharded_run_round(grid, props, shift, (th, tw), 0.3, 0.6,
                                  dom, mesh,
                                  local_kernel={local_kernel!r})
            assert jnp.array_equal(a, b), f"round {{r}} diverged"
            grid = a
        print("EXACT_MATCH")
    """, n_devices=16)
    assert "EXACT_MATCH" in out


@pytest.mark.slow
def test_sharded_simulation_runs(subproc):
    out = subproc("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import dominance as dm, metrics
        from repro.core.lattice import init_grid
        from repro.core.params import EscgParams
        from repro.core.sharded import make_sharded_simulation
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((2, 4), ("data", "model"))
        p = EscgParams(length=64, height=32, species=3, mobility=1e-4,
                       engine="sublattice", tile=(8, 16), seed=0)
        grid_sh, one_mcs = make_sharded_simulation(p, dm.RPS(), mesh)
        key = jax.random.PRNGKey(0)
        grid = jax.device_put(init_grid(key, 32, 64, 3, 0.1), grid_sh)
        for i in range(5):
            key, k = jax.random.split(key)
            grid = one_mcs(grid, k)
        c = metrics.counts(grid, 3)
        assert int(c.sum()) == 32 * 64
        print("OK", np.asarray(c))
    """, n_devices=8)
    assert "OK" in out


@pytest.mark.slow
def test_sharded_shard_count_invariance(subproc):
    """Conserved cell counts and identical survivor statistics across shard
    layouts on 4 fake devices — the trajectory is a function of (key, tile
    id) only, so every decomposition is bit-identical."""
    out = subproc("""
        import jax, numpy as np
        from repro.core import EscgParams, dominance as dm, simulate
        kw = dict(length=64, height=32, species=5, mcs=4, chunk_mcs=2,
                  tile=(8, 16), seed=3, mobility=1e-3, empty=0.1)
        base = simulate(EscgParams(engine="sublattice", **kw),
                        dm.RPSLS(), stop_on_stasis=False)
        n0 = base.densities[0].sum()
        for sg in ((1, 1), (2, 2), (4, 1), (1, 4), (2, 1)):
            r = simulate(EscgParams(engine="sharded", shard_grid=sg, **kw),
                         dm.RPSLS(), stop_on_stasis=False)
            assert np.array_equal(r.grid, base.grid), sg
            assert np.array_equal(r.densities, base.densities), sg
            # conservation: every MCS's counts sum to N
            assert np.allclose(r.densities.sum(axis=1), n0), sg
            surv = r.densities[-1][1:] > 0
            assert np.array_equal(surv, base.densities[-1][1:] > 0), sg
        print("SHARD_INVARIANT")
    """, n_devices=4)
    assert "SHARD_INVARIANT" in out


@pytest.mark.slow
def test_sharded_256_grid_across_4_devices(subproc):
    """Acceptance: a 256x256 grid runs device-resident across 4 fake CPU
    devices with counts matching a single-device run."""
    out = subproc("""
        import numpy as np
        from repro.core import EscgParams, simulate
        kw = dict(length=256, height=256, species=3, mcs=2, chunk_mcs=2,
                  tile=(8, 16), seed=0, mobility=1e-4, empty=0.1)
        multi = simulate(EscgParams(engine="sharded", shard_grid=(2, 2),
                                    **kw), stop_on_stasis=False)
        single = simulate(EscgParams(engine="sharded", shard_grid=(1, 1),
                                     **kw), stop_on_stasis=False)
        assert np.array_equal(multi.grid, single.grid)
        assert np.array_equal(multi.densities, single.densities)
        assert int(multi.densities[-1].sum() * 256 * 256) == 256 * 256
        print("OK_256", np.round(multi.densities[-1], 4))
    """, n_devices=4)
    assert "OK_256" in out


@pytest.mark.slow
def test_halo_roll_matches_global_roll(subproc):
    """The ppermute halo exchange equals a global torus roll, under jit,
    for every shift — including the jax-0.4.x pattern (roll of a shard_map
    output) that miscompiles and motivated the in-region design."""
    out = subproc("""
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.core.sharded import shard_shift2d
        from repro.parallel.sharding import lattice_mesh

        mesh = lattice_mesh((2, 2), 32, 64, 8, 16)
        x = jnp.arange(32 * 64, dtype=jnp.int32).reshape(32, 64)

        @partial(jax.jit, static_argnums=2)
        def roll(x, s, reverse):
            f = partial(shard_shift2d, tile_shape=(8, 16), shard_grid=(2, 2),
                        reverse=reverse)
            return shard_map(f, mesh=mesh, in_specs=(P("rows", "cols"), P()),
                             out_specs=P("rows", "cols"),
                             check_vma=False)(x, s)

        for sy in (0, 3, 7):
            for sx in (0, 5, 15):
                s = jnp.array([sy, sx], jnp.int32)
                want = np.roll(np.asarray(x), (-sy, -sx), (0, 1))
                got = np.asarray(roll(x, s, False))
                assert np.array_equal(got, want), (sy, sx)
                back = np.asarray(roll(jnp.asarray(got), s, True))
                assert np.array_equal(back, np.asarray(x)), (sy, sx, "rev")
        print("HALO_OK")
    """, n_devices=4)
    assert "HALO_OK" in out


@pytest.mark.slow
def test_vmapped_trials_over_pod_axis(subproc):
    """IID ESCG trials sharded over a 'pod' axis (the multi-pod statistics
    story, DESIGN.md §5)."""
    out = subproc("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import dominance as dm
        from repro.core.lattice import init_grid
        from repro.core.params import EscgParams
        from repro.core.simulation import build_mcs_fn
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((4, 2), ("pod", "data"))
        p = EscgParams(length=16, height=16, species=3, mobility=1e-4,
                       engine="batched", seed=0)
        one = build_mcs_fn(p, jnp.asarray(dm.RPS()))
        def trial(grid, key):
            for i in range(3):
                key, k = jax.random.split(key)
                grid, _, _ = one(grid, k)
            return grid
        keys = jax.random.split(jax.random.PRNGKey(0), 8)
        grids = jax.vmap(lambda k: init_grid(k, 16, 16, 3, 0.1))(keys)
        grids = jax.device_put(grids,
                               NamedSharding(mesh, P("pod", "data", None)))
        out = jax.jit(jax.vmap(trial))(grids, keys)
        assert out.shape == (8, 16, 16)
        print("PODS_OK")
    """, n_devices=8)
    assert "PODS_OK" in out


@pytest.mark.slow
def test_composed_mesh_cli_path(subproc):
    """--trials + --engine sharded_pod --meshShape drives the composed
    mesh end-to-end through the CLI entry point."""
    out = subproc("""
        import sys
        sys.argv = ["escg_run", "--length", "32", "--height", "32",
                    "--species", "5", "--mcs", "4", "--chunkMcs", "2",
                    "--tile", "8", "8", "--trials", "4",
                    "--engine", "sharded_pod", "--meshShape", "2,2,2",
                    "--mobility", "0.001"]
        from repro.launch.escg_run import main
        main()
    """, n_devices=8)
    assert "survival probabilities" in out
