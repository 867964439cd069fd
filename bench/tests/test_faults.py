"""A whole run through the harness, its look for a chip skipped, with the
timed path broken underneath: ``correct`` has to come out false for each
fault a cell can have. Both cells run on one chip, so no cell has an
exchange between chips to leave out."""
import jax
import jax.numpy as jnp
import pytest

from repro.core import engines, metrics, trials

from bench_cases import LATTICE, TRIALS, run_cell, small_root

CELLS = {"trials": f"{TRIALS}.sublattice", "lattice": f"{LATTICE}.pallas_fused"}
ENGINES = {"trials": "sublattice", "lattice": "pallas_fused"}


def _patch_step(monkeypatch, engine, wrap):
    """Re-register ``engine`` with its one-MCS step wrapped by ``wrap(p,
    step)``."""
    spec = engines.get_engine(engine)

    def build(p, dom):
        built = spec.build(p, dom)
        return built._replace(one_mcs=wrap(p, built.one_mcs))

    monkeypatch.setitem(engines._REGISTRY, engine, engines.EngineSpec(
        name=engine, caps=spec.caps, build=build))


def state_unchanged(p, step):
    def one_mcs(grid, key):
        _, kept, att = step(grid, key)
        return grid, kept, att
    return one_mcs


def answer_altered(p, step):
    def one_mcs(grid, key):
        grid, kept, att = step(grid, key)
        return grid.at[0, 0].set(grid[0, 0] % p.species + 1), kept, att
    return one_mcs


def half_lattice_left_out(p, step):
    def one_mcs(grid, key):
        new, kept, att = step(grid, key)
        rows = jax.lax.broadcasted_iota(jnp.int32, grid.shape, 0)
        return jnp.where(rows < grid.shape[0] // 2, new, grid), kept, att
    return one_mcs


def _half_batch_left_out(monkeypatch):
    """The trial chunk advances the first half of the batch and hands the
    second half back as it came."""
    inner_build = trials.build_trial_chunk

    def build(p, dom, *a, **kw):
        inner = inner_build(p, dom, *a, **kw)

        def chunk(grids, keys, n_mcs):
            h = grids.shape[0] // 2
            g, k, cnts, alive, kept, att = inner(grids[:h], keys[:h], n_mcs)
            rest = jax.vmap(lambda x: metrics.counts(x, p.species))(
                grids[h:])
            rest_alive = jnp.repeat((rest[:, None, 1:] > 0), n_mcs, axis=1)
            zeros = jnp.zeros((grids.shape[0] - h,), kept.dtype)
            return (jnp.concatenate([g, grids[h:]]),
                    jnp.concatenate([k, keys[h:]]),
                    jnp.concatenate([cnts, rest]),
                    jnp.concatenate([alive, rest_alive]),
                    jnp.concatenate([kept, zeros]),
                    jnp.concatenate([att, zeros]))
        return chunk

    monkeypatch.setattr(trials, "build_trial_chunk", build)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return small_root(tmp_path_factory.mktemp("cells"))


@pytest.mark.parametrize("kind", ["trials", "lattice"])
def test_sound_run_is_correct(root, kind, capsys):
    rc, result = run_cell(root, CELLS[kind], capsys=capsys)
    assert rc == 0 and result["correct"] is True
    assert result["failed"] == 0
    assert all(c["value"] == 0 for c in result["check"].values())


@pytest.mark.parametrize("kind", ["trials", "lattice"])
@pytest.mark.parametrize("fault", [state_unchanged, answer_altered],
                         ids=["state_unchanged", "answer_altered"])
def test_fault_in_the_step_is_caught(root, kind, fault, monkeypatch,
                                     capsys):
    _patch_step(monkeypatch, ENGINES[kind], fault)
    rc, result = run_cell(root, CELLS[kind], capsys=capsys)
    assert rc == 0 and result["correct"] is False
    assert result["failed"] > 0


def test_half_the_trial_batch_left_out_is_caught(root, monkeypatch, capsys):
    _half_batch_left_out(monkeypatch)
    rc, result = run_cell(root, CELLS["trials"], capsys=capsys)
    assert rc == 0 and result["correct"] is False
    assert result["check"]["trials_differing"]["value"] > 0


def test_half_the_lattice_left_out_is_caught(root, monkeypatch, capsys):
    _patch_step(monkeypatch, ENGINES["lattice"], half_lattice_left_out)
    rc, result = run_cell(root, CELLS["lattice"], capsys=capsys)
    assert rc == 0 and result["correct"] is False
    assert result["check"]["cells_differing"]["value"] > 0
