"""Pallas kernels vs pure-jnp oracles (interpret mode), with shape sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dominance as dm
from repro.core.lattice import init_grid
from repro.core.rng import tile_proposal_batch
from repro.kernels import ops, ref

KNOWN_ANSWER = {
    # Random123 published KAT: philox4x32-10, zero counter / zero key
    (0, 0): (0x6627E8D5, 0xE169C58D, 0xBC57AC4C, 0x9B00DBD8),
}


# ------------------------------- philox ---------------------------------- #

def test_philox_known_answer():
    x = ref.philox4x32_ref(np.zeros(1, np.uint32), np.zeros(1, np.uint32),
                           np.zeros(1, np.uint32), np.zeros(1, np.uint32),
                           0, 0)
    got = tuple(int(v[0]) for v in x)
    assert got == KNOWN_ANSWER[(0, 0)]


@pytest.mark.parametrize("n", [1, 4, 100, 4096, 5000])
@pytest.mark.parametrize("seed", [(0, 0), (0xDEADBEEF, 0x12345678)])
def test_philox_kernel_matches_ref(n, seed):
    got = np.asarray(ops.philox_bits(n, seed=seed, stream=3, block=256))
    want = ref.philox_bits_ref(n, seed, stream=3, block=256)
    assert got.shape == (n,)
    np.testing.assert_array_equal(got, want)


def test_philox_uniform_range_and_mean():
    u = np.asarray(ops.philox_uniform(200_000, seed=(1, 2)))
    assert u.min() >= 0.0 and u.max() < 1.0
    assert abs(u.mean() - 0.5) < 0.005
    assert abs(u.var() - 1 / 12) < 0.005


def test_philox_streams_decorrelated():
    a = np.asarray(ops.philox_bits(10_000, seed=(5, 5), stream=0))
    b = np.asarray(ops.philox_bits(10_000, seed=(5, 5), stream=1))
    assert not np.array_equal(a, b)
    # correlation of uniforms ~ 0
    ua, ub = a / 2**32, b / 2**32
    assert abs(np.corrcoef(ua, ub)[0, 1]) < 0.05


# ----------------------------- escg update ------------------------------- #

@pytest.mark.parametrize("hw,tile,species,nbhd", [
    ((16, 32), (8, 16), 3, 4),
    ((24, 24), (8, 8), 5, 8),
    ((8, 128), (4, 32), 2, 4),
    ((32, 64), (16, 16), 8, 4),
])
def test_escg_kernel_matches_oracle(hw, tile, species, nbhd):
    h, w = hw
    th, tw = tile
    key = jax.random.PRNGKey(h * w + species)
    grid = init_grid(key, h, w, species, 0.15)
    offs = (1, 2) if species >= 5 else (1,)
    dom = jnp.asarray(dm.circulant(species, offs) if species > 1 else
                      dm.from_dense(np.zeros((1, 1), np.float32)))
    nt = (h // th) * (w // tw)
    k = 53
    props = tile_proposal_batch(jax.random.PRNGKey(1), nt, k,
                                (th - 2) * (tw - 2), nbhd)
    te, tem = 0.25, 0.6
    shift = jnp.array([th // 2, tw // 3], jnp.int32)
    got = ops.escg_round(grid, props, shift, dom, tile, te, tem)
    rolled = jnp.roll(grid, (-shift[0], -shift[1]), (0, 1))
    want = ref.escg_tile_round_ref(rolled, props.cell, props.dirn,
                                   props.u_act, props.u_dom, dom, tile, te,
                                   tem)
    want = jnp.roll(want, (shift[0], shift[1]), (0, 1))
    assert jnp.array_equal(got, want)


def test_escg_kernel_probabilistic_dominance():
    """Park-style fractional rates flow through the kernel identically."""
    h, w, th, tw = 16, 16, 8, 8
    grid = init_grid(jax.random.PRNGKey(0), h, w, 8, 0.0)
    dom = jnp.asarray(dm.park_alliance_network(0.3, 0.75, 1.0))
    props = tile_proposal_batch(jax.random.PRNGKey(2), 4, 40,
                                (th - 2) * (tw - 2), 4)
    shift = jnp.array([0, 0], jnp.int32)
    got = ops.escg_round(grid, props, shift, dom, (th, tw), 0.0, 0.9)
    want = ref.escg_tile_round_ref(grid, props.cell, props.dirn,
                                   props.u_act, props.u_dom, dom, (th, tw),
                                   0.0, 0.9)
    assert jnp.array_equal(got, want)


def test_escg_kernel_in_simulation_engine():
    """engine='pallas' must track engine='sublattice' exactly (same keys)."""
    from repro.core import EscgParams, simulate
    kw = dict(length=32, height=16, species=3, mcs=8, tile=(8, 16),
              chunk_mcs=4, empty=0.1, seed=5, mobility=1e-3)
    r1 = simulate(EscgParams(engine="sublattice", **kw), stop_on_stasis=False)
    r2 = simulate(EscgParams(engine="pallas", **kw), stop_on_stasis=False)
    np.testing.assert_array_equal(r1.grid, r2.grid)
    np.testing.assert_allclose(r1.densities, r2.densities, atol=0)


# ------------------------------- density --------------------------------- #

@pytest.mark.parametrize("hw,species", [((8, 16), 3), ((32, 128), 5),
                                        ((17, 33), 8), ((64, 64), 1)])
def test_density_kernel(hw, species):
    grid = init_grid(jax.random.PRNGKey(11), hw[0], hw[1], species, 0.3)
    got = np.asarray(ops.density_counts(grid, species))
    want = np.asarray(ref.density_ref(grid, species))
    np.testing.assert_array_equal(got, want)
    assert got.sum() == hw[0] * hw[1]


# --------------------------- fused-PRNG kernel ---------------------------- #

@pytest.mark.parametrize("hw,tile,species,nbhd,seed,cell_dtype", [
    pytest.param((32, 64), (8, 16), 5, 4, (0xABCD1234, 0x5678DEAD), "int32",
                 id="hw0-tile0-5-4-seed0"),
    pytest.param((16, 16), (8, 8), 3, 8, (1, 2), "int32",
                 id="hw1-tile1-3-8-seed1"),
    pytest.param((24, 48), (8, 16), 8, 4, (0, 0), "int32",
                 id="hw2-tile2-8-4-seed2"),
    # 160 tiles: one block of two 128-lane columns, 96 of them dummy
    pytest.param((64, 320), (8, 16), 3, 4, (0x9E3779B9, 7), "int32",
                 id="160_tiles_two_columns"),
    # 512 tiles: two blocks of 256 lanes
    pytest.param((64, 512), (8, 8), 3, 8, (5, 0xFFFFFFFF), "int32",
                 id="512_tiles_two_blocks"),
    # 384 tiles: three blocks of 128 lanes (256-lane padding would cost more)
    pytest.param((48, 512), (8, 8), 5, 4, (11, 12), "int32",
                 id="384_tiles_three_blocks"),
    pytest.param((32, 64), (8, 16), 5, 4, (0xABCD1234, 0x5678DEAD), "int8",
                 id="int8"),
])
def test_escg_fused_kernel_matches_host_philox_oracle(hw, tile, species,
                                                      nbhd, seed, cell_dtype):
    """In-kernel Philox proposal derivation == host-side derivation feeding
    the standard tile oracle (bit-exact). K = 61 is not a multiple of the
    kernel's batch of eight proposals."""
    h, w = hw
    th, tw = tile
    grid = init_grid(jax.random.PRNGKey(h + species), h, w, species, 0.1,
                     dtype=jnp.dtype(cell_dtype))
    offs = (1, 2) if species >= 5 else (1,)
    dom = jnp.asarray(dm.circulant(species, offs))
    nt = (h // th) * (w // tw)
    k = 61
    seed_arr = jnp.asarray(np.array(seed, np.uint32))
    shift = jnp.array([3, 5], jnp.int32)
    got = ops.escg_round_fused(grid, seed_arr, jnp.uint32(7), shift, dom,
                               tile, k, 0.25, 0.6, nbhd)
    cell, dirn, ua, ud = ref.fused_proposals_ref(
        nt, k, (th - 2) * (tw - 2), nbhd, seed, 7)
    rolled = jnp.roll(grid, (-3, -5), (0, 1))
    want = ref.escg_tile_round_ref(rolled, jnp.asarray(cell),
                                   jnp.asarray(dirn), jnp.asarray(ua),
                                   jnp.asarray(ud), dom, tile, 0.25, 0.6)
    want = jnp.roll(want, (3, 5), (0, 1))
    assert got.dtype == grid.dtype
    assert jnp.array_equal(got, want)


def test_fused_lane_layout():
    """Two 128-lane columns per block unless their padding makes more
    than 8/7 as many lanes; the padded count is whole blocks."""
    from repro.kernels.escg_update_fused import lane_layout
    assert lane_layout(4) == (128, 128)
    assert lane_layout(160) == (256, 256)
    assert lane_layout(384) == (128, 384)
    assert lane_layout(40_000) == (256, 40_192)


@pytest.mark.parametrize("hw,tile,species,nbhd,k_steps", [
    ((16, 32), (8, 16), 5, 4, 3),
    ((16, 16), (8, 8), 3, 8, 4),
])
def test_escg_megakernel_matches_sequential_fused_rounds(hw, tile, species,
                                                         nbhd, k_steps):
    """K grid-resident MCS in ONE pallas_call (escg_rounds_fused) must be
    bit-identical to K single-round fused kernels run back-to-back in the
    drifting frame (roll_back=False), and its in-kernel per-step species
    counts must equal metrics.counts after every step — the k_mcs
    megakernel contract (DESIGN.md §6)."""
    from repro.core import metrics
    h, w = hw
    k = 61
    grid = init_grid(jax.random.PRNGKey(h + species), h, w, species, 0.1)
    offs = (1, 2) if species >= 5 else (1,)
    dom = jnp.asarray(dm.circulant(species, offs))
    rng = np.random.RandomState(7)
    seeds = jnp.asarray(
        rng.randint(0, 2**32, size=(k_steps, 2), dtype=np.uint32))
    shifts = jnp.asarray(np.stack(
        [rng.randint(0, tile[0], k_steps),
         rng.randint(0, tile[1], k_steps)], axis=1).astype(np.int32))
    got_g, got_c = ops.escg_rounds_fused(grid, seeds, shifts, dom, tile, k,
                                         0.25, 0.6, species, nbhd)
    assert got_c.shape == (k_steps, species + 1)
    g = grid
    for t in range(k_steps):
        g = ops.escg_round_fused(g, seeds[t], jnp.uint32(0), shifts[t],
                                 dom, tile, k, 0.25, 0.6, nbhd,
                                 roll_back=False)
        np.testing.assert_array_equal(
            np.asarray(got_c[t]), np.asarray(metrics.counts(g, species)),
            err_msg=f"step {t} counts")
    np.testing.assert_array_equal(np.asarray(got_g), np.asarray(g))


def test_fused_counter_capacity_guard():
    """tile_id * k + j is a uint32 counter: a tiling whose proposal space
    exceeds 2^32 must be rejected loudly, never wrapped silently."""
    from repro.kernels.escg_update_fused import check_counter_capacity
    check_counter_capacity(1 << 16, 1 << 16)          # exactly 2^32: legal
    with pytest.raises(ValueError, match="counter"):
        check_counter_capacity((1 << 16) + 1, 1 << 16)


def test_escg_fused_engine_runs_and_conserves():
    from repro.core import EscgParams, simulate
    p = EscgParams(length=32, height=16, species=4, mcs=10, mu=0.0,
                   sigma=0.0, epsilon=1.0, engine="pallas_fused",
                   tile=(8, 16), chunk_mcs=5, empty=0.25, seed=3)
    r = simulate(p, dm.circulant(4), stop_on_stasis=False)
    np.testing.assert_allclose(r.densities[0], r.densities[-1], atol=1e-9)
