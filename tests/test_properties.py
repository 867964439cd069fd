"""Property-based invariants of the composed mesh machinery (DESIGN.md §6).

Two layers, matching the repo's device-count test policy (conftest):

* fast host-level properties (hypothesis, or the deterministic
  ``_propcheck`` fallback) exercise the single-shard paths;
* ``slow`` subprocess properties run the REAL multi-device paths on fake
  CPU devices, drawing their examples from a seeded ``random.Random`` so
  every CI run replays the same cases — the acceptance property is that
  ``run_trials`` on ANY random (P, R, C) factorization of 8 devices is
  bit-identical to the (1, 1, 1) layout (and hence, via
  tests/test_engine_equivalence.py, to the single-device ``sublattice``
  engine).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:   # hermetic container: deterministic fallback sampler
    from _propcheck import given, settings, strategies as st

from repro.core.sharded import halo_roll

pytestmark = pytest.mark.composed   # re-run by the CI 8-fake-device job


# ------------------------- fast host-level layer -------------------------- #

@given(extent=st.sampled_from([8, 16, 24]), s=st.integers(0, 7),
       axis=st.sampled_from([0, 1]), reverse=st.booleans())
@settings(max_examples=20, deadline=None)
def test_halo_roll_single_shard_is_torus_roll(extent, s, axis, reverse):
    """n_shards=1 collapses halo_roll to a plain torus roll, and
    forward-then-reverse is the identity for every shift."""
    x = jnp.arange(extent * extent, dtype=jnp.int32).reshape(extent, extent)
    sh = jnp.int32(s)
    fwd = halo_roll(x, sh, halo=8, axis_name="rows", axis=axis, n_shards=1)
    want = np.roll(np.asarray(x), s if reverse else -s, axis)
    got = (halo_roll(x, sh, 8, "rows", axis, 1, reverse=True)
           if reverse else fwd)
    assert np.array_equal(np.asarray(got), want)
    back = halo_roll(fwd, sh, 8, "rows", axis, 1, reverse=True)
    assert np.array_equal(np.asarray(back), np.asarray(x))


@given(p=st.integers(1, 4), r=st.integers(1, 2), c=st.integers(1, 2),
       n=st.integers(1, 17))
@settings(max_examples=25, deadline=None)
def test_padding_is_pod_width_only(p, r, c, n):
    """The composed batch pads to a multiple of the pod width P alone —
    grid-axis factors shard H/W, never the trial axis."""
    from repro.core.trials import pad_trials
    n_pad = pad_trials(n, p)
    assert n_pad >= n and n_pad % p == 0 and n_pad - n < p


# --------------------------- multi-device layer --------------------------- #

@pytest.mark.slow
def test_halo_roll_round_trip_random_shifts(subproc):
    """Property: on a (2, 2) device mesh, shard_shift2d for ANY random
    shift equals the global torus roll, and forward-then-reverse is the
    identity (seeded sampling over the full [0,th) x [0,tw) range)."""
    out = subproc("""
        import random
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.core.sharded import shard_shift2d
        from repro.parallel.sharding import lattice_mesh

        th, tw = 8, 16
        mesh = lattice_mesh((2, 2), 32, 64, th, tw)
        x = jnp.arange(32 * 64, dtype=jnp.int32).reshape(32, 64)

        @partial(jax.jit, static_argnums=2)
        def roll(x, s, reverse):
            f = partial(shard_shift2d, tile_shape=(th, tw),
                        shard_grid=(2, 2), reverse=reverse)
            return shard_map(f, mesh=mesh,
                             in_specs=(P("rows", "cols"), P()),
                             out_specs=P("rows", "cols"),
                             check_vma=False)(x, s)

        rng = random.Random("halo_roll_round_trip")
        for i in range(12):
            sy, sx = rng.randrange(th), rng.randrange(tw)
            s = jnp.array([sy, sx], jnp.int32)
            got = np.asarray(roll(x, s, False))
            want = np.roll(np.asarray(x), (-sy, -sx), (0, 1))
            assert np.array_equal(got, want), ("fwd", i, sy, sx)
            back = np.asarray(roll(jnp.asarray(got), s, True))
            assert np.array_equal(back, np.asarray(x)), ("rev", i, sy, sx)
        print("HALO_PROPERTY_OK")
    """, n_devices=4)
    assert "HALO_PROPERTY_OK" in out


@pytest.mark.slow
def test_mesh_factorization_invariance(subproc):
    """Acceptance property: run_trials over a composed ('pod','rows',
    'cols') mesh is bit-identical to the (1,1,1) layout for random legal
    factorizations of 8 fake devices — trial keys and tile streams are
    functions of global identity only, never of the layout."""
    out = subproc("""
        import random
        import numpy as np
        from repro.core import EscgParams, dominance as dm
        from repro.core.trials import run_trials

        kw = dict(length=32, height=32, species=5, mobility=1e-3,
                  tile=(8, 8), empty=0.1, seed=13, engine='sharded_pod')
        dom = dm.RPSLS()

        def run(ms):
            return run_trials(EscgParams(mesh_shape=ms, **kw), dom,
                              n_trials=5, n_mcs=4, chunk_mcs=2,
                              stop_on_stasis=False)

        base = run((1, 1, 1))
        # every (P, R, C) with P*R*C == 8 that the 32x32/tile(8,8)
        # lattice admits (rows, cols must split it into tile multiples)
        legal = [(p, r, c)
                 for p in (1, 2, 4, 8) for r in (1, 2, 4) for c in (1, 2, 4)
                 if p * r * c == 8]
        assert len(legal) >= 6, legal
        rng = random.Random("mesh_factorization")
        for ms in rng.sample(legal, 5):
            r = run(ms)
            assert r.n_devices == 8, ms
            assert np.array_equal(r.survival, base.survival), ms
            assert np.array_equal(r.densities, base.densities), ms
            assert np.array_equal(r.stasis_mcs, base.stasis_mcs), ms
            assert np.array_equal(r.extinction_mcs,
                                  base.extinction_mcs), ms
        print("FACTORIZATION_INVARIANT")
    """, n_devices=8)
    assert "FACTORIZATION_INVARIANT" in out


@pytest.mark.slow
def test_fused_local_kernel_factorization_invariance(subproc):
    """Acceptance property for the fused-Philox family: run_trials with
    ``engine='sharded_pod', local_kernel='fused'`` on ANY random legal
    (P, R, C) factorization of 8 fake devices is bit-identical to the
    (1, 1, 1) layout AND to the single-device ``pallas_fused`` engine's
    pod-sharded trial batch — in-kernel counters are keyed by global
    (trial, tile) identity only, never by the mesh layout."""
    out = subproc("""
        import random
        import numpy as np
        from repro.core import EscgParams, dominance as dm
        from repro.core.trials import run_trials

        kw = dict(length=32, height=32, species=5, mobility=1e-3,
                  tile=(8, 8), empty=0.1, seed=17)
        dom = dm.RPSLS()

        def run(engine, ms=None, lk='jnp'):
            return run_trials(EscgParams(engine=engine, mesh_shape=ms,
                                         local_kernel=lk, **kw), dom,
                              n_trials=5, n_mcs=4, chunk_mcs=2,
                              stop_on_stasis=False)

        oracle = run('pallas_fused')            # vmapped, pod-sharded
        base = run('sharded_pod', (1, 1, 1), 'fused')
        for f in ('survival', 'densities', 'stasis_mcs', 'extinction_mcs'):
            assert np.array_equal(getattr(base, f), getattr(oracle, f)), f

        legal = [(p, r, c)
                 for p in (1, 2, 4, 8) for r in (1, 2, 4) for c in (1, 2, 4)
                 if p * r * c == 8]
        rng = random.Random("fused_factorization")
        for ms in rng.sample(legal, 5):
            r = run('sharded_pod', ms, 'fused')
            assert r.n_devices == 8, ms
            assert np.array_equal(r.survival, oracle.survival), ms
            assert np.array_equal(r.densities, oracle.densities), ms
            assert np.array_equal(r.stasis_mcs, oracle.stasis_mcs), ms
            assert np.array_equal(r.extinction_mcs,
                                  oracle.extinction_mcs), ms
        print("FUSED_FACTORIZATION_INVARIANT")
    """, n_devices=8)
    assert "FUSED_FACTORIZATION_INVARIANT" in out


@pytest.mark.slow
def test_k_mcs_megakernel_factorization_invariance(subproc):
    """Acceptance property for the multi-MCS megakernel: k_mcs > 1 on
    ``sharded_pod / local_kernel='fused'`` is bit-identical to the
    single-device ``pallas_fused`` k_mcs=1 run on EVERY sampled (P, R, C)
    factorization of 8 fake devices. n_mcs=4 with chunk_mcs=3 and
    k_mcs=2 drives both grouped-scan shapes (3 = one group + remainder,
    then a bare-remainder chunk of 1); (P, 1, 1) layouts run the true
    single-pallas_call megakernel, multi-shard layouts the K-kernels-one-
    region fallback — same contract either way."""
    out = subproc("""
        import numpy as np
        from repro.core import EscgParams, dominance as dm
        from repro.core.trials import run_trials

        kw = dict(length=32, height=32, species=5, mobility=1e-3,
                  tile=(8, 8), empty=0.1, seed=17)
        dom = dm.RPSLS()

        def run(engine, ms=None, lk='jnp', k=1):
            return run_trials(EscgParams(engine=engine, mesh_shape=ms,
                                         local_kernel=lk, k_mcs=k, **kw),
                              dom, n_trials=5, n_mcs=4, chunk_mcs=3,
                              stop_on_stasis=False)

        oracle = run('pallas_fused')
        for ms in ((8, 1, 1), (2, 2, 2), (1, 2, 4), (4, 1, 2)):
            for k in (2, 3):
                r = run('sharded_pod', ms, 'fused', k)
                assert r.n_devices == 8, (ms, k)
                assert np.array_equal(r.survival, oracle.survival), (ms, k)
                assert np.array_equal(r.densities,
                                      oracle.densities), (ms, k)
                assert np.array_equal(r.stasis_mcs,
                                      oracle.stasis_mcs), (ms, k)
                assert np.array_equal(r.extinction_mcs,
                                      oracle.extinction_mcs), (ms, k)
        print("K_MCS_FACTORIZATION_INVARIANT")
    """, n_devices=8)
    assert "K_MCS_FACTORIZATION_INVARIANT" in out


@pytest.mark.slow
def test_composed_pallas_local_kernel_matches_jnp(subproc):
    """The acceptance pairing: local_kernel='pallas' inside the composed
    shard_map region is bit-identical to the jnp sweeps, for both the
    sharded and sharded_pod engines."""
    out = subproc("""
        import numpy as np
        from repro.core import EscgParams, dominance as dm, simulate
        from repro.core.trials import run_trials

        kw = dict(length=32, height=32, species=5, mobility=1e-3,
                  tile=(8, 8), empty=0.1, seed=2)
        dom = dm.RPSLS()
        a = simulate(EscgParams(engine='sharded', shard_grid=(2, 2),
                                local_kernel='jnp', mcs=3, chunk_mcs=3,
                                **kw), dom, stop_on_stasis=False)
        b = simulate(EscgParams(engine='sharded', shard_grid=(2, 2),
                                local_kernel='pallas', mcs=3, chunk_mcs=3,
                                **kw), dom, stop_on_stasis=False)
        assert np.array_equal(a.grid, b.grid)
        assert np.array_equal(a.densities, b.densities)

        rj = run_trials(EscgParams(engine='sharded_pod',
                                   mesh_shape=(2, 2, 2), **kw),
                        dom, 3, n_mcs=3, stop_on_stasis=False)
        rp = run_trials(EscgParams(engine='sharded_pod',
                                   mesh_shape=(2, 2, 2),
                                   local_kernel='pallas', **kw),
                        dom, 3, n_mcs=3, stop_on_stasis=False)
        assert np.array_equal(rj.survival, rp.survival)
        assert np.array_equal(rj.densities, rp.densities)
        assert np.array_equal(rj.extinction_mcs, rp.extinction_mcs)
        print("LOCAL_KERNEL_BIT_IDENTICAL")
    """, n_devices=8)
    assert "LOCAL_KERNEL_BIT_IDENTICAL" in out
