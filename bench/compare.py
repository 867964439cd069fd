"""The comparisons that decide ``correct``, shared by the drivers.

A cell's driver (``bench.drivers``) follows the run with the plain
reference (``bench.references``) from the same seed after the window,
compares what the timed call produced with it by the functions here, and
holds the limit of each number compared. Every number compared is a
count of answers that differ, and every limit of the drivers here is 0:
the engines are exact integer simulations whose random streams are
functions of the seed, so a sound run reproduces the reference cell for
cell.

- Trial batches: a sample of trials drawn from the seed
  (``sample_trials``). For each, the final species counts, the
  alive-species count the driver streamed to the hook after every chunk,
  and the per-trial extinction MCS, stasis MCS and survival
  (``check_trials``).
- Single lattices: the final lattice cell for cell, and every step's
  species counts, as the result's density stream holds them and as the
  driver streamed them to the hook after every chunk; and, where the
  configuration streams it, every step's interface length, as the
  unlike-bond count that the share carries (share × 2 N, to the nearest
  bond) against the reference's count (``check_single``).
"""
from __future__ import annotations

import numpy as np


def sample_trials(n_trials: int, sample: int, seed: int) -> np.ndarray:
    """``sample`` distinct trial indices drawn from the seed, sorted."""
    rng = np.random.default_rng(seed)
    return np.sort(rng.choice(n_trials, size=min(sample, n_trials),
                              replace=False))


def _first_mcs(mask: np.ndarray) -> np.ndarray:
    """First step (1-based, along axis 1) at which ``mask`` holds; -1 if
    never."""
    hit = mask.any(axis=1)
    return np.where(hit, mask.argmax(axis=1) + 1, -1)


def trial_answers(ref: np.ndarray, chunk_mcs: int) -> dict:
    """What the trial driver reports, worked out from reference counts
    ``ref`` (trials, M + 1, S + 1)."""
    alive = ref[:, :, 1:] > 0
    n_alive = alive.sum(axis=2)
    ext = _first_mcs(~alive[:, 1:])
    ext = np.where(~alive[:, 0], 0, ext)
    return {
        "final_counts": ref[:, -1],
        "alive": n_alive[:, chunk_mcs::chunk_mcs],
        "extinction_mcs": ext,
        "stasis_mcs": _first_mcs(n_alive[:, 1:] <= 1),
        "survival": alive[:, -1],
    }


def check_trials(got: dict, ref: np.ndarray, ids: np.ndarray,
                 chunk_mcs: int) -> dict:
    """Compare the sampled trials ``ids`` of the timed call's answers
    ``got`` with the reference counts of the same trials."""
    want = trial_answers(ref, chunk_mcs)
    wrong = np.zeros(len(ids), bool)
    for key, w in want.items():
        g = np.asarray(got[key])[ids]
        if g.shape != w.shape:
            wrong[:] = True
            continue
        wrong |= (g != w).reshape(len(ids), -1).any(axis=1)
    return {"trials_differing": int(wrong.sum())}


def interface_share(bonds: np.ndarray, n_cells: int) -> np.ndarray:
    """The ``interface_length`` stream: each step's unlike-bond count over
    the 2 N bonds of the torus, one row per step."""
    return bonds.astype(np.float64)[:, None] / (2.0 * n_cells)


def interface_bonds(share: np.ndarray, n_cells: int) -> np.ndarray:
    """The unlike-bond counts an ``interface_length`` stream carries: the
    share times the 2 N bonds, to the nearest whole bond."""
    return np.rint(np.asarray(share, np.float64) * (2.0 * n_cells)).astype(
        np.int64)


def check_single(got: dict, ref_counts: np.ndarray, ref_bonds: np.ndarray,
                 ref_grid: np.ndarray, observables=()) -> dict:
    """Compare a single lattice's final cells and its per-step streams:
    the species counts, and the interface length where ``observables``
    names it."""
    steps = ref_counts.shape[0] - 1
    rows_wrong = np.zeros(steps, bool)
    streams = [("counts", ref_counts[1:]), ("hook_counts", ref_counts[1:])]
    got = dict(got)
    if "interface_length" in observables:
        # the stream holds the count as a float32, which rounds past 2**24
        streams.append(("interface_length", ref_bonds.astype(np.float32)
                        .astype(np.int64)[:, None]))
        if got["interface_length"] is not None:
            got["interface_length"] = interface_bonds(
                got["interface_length"], ref_grid.size)
    for key, want in streams:
        g = got[key]
        g = np.asarray(g) if g is not None else None
        if g is None or g.shape != want.shape:
            rows_wrong[:] = True
            continue
        rows_wrong |= (g != want).any(axis=1)
    initial_wrong = not np.array_equal(np.asarray(got["initial_counts"]),
                                       ref_counts[0])
    grid = np.asarray(got["grid"])
    cells = (int((grid != ref_grid).sum()) if grid.shape == ref_grid.shape
             else int(ref_grid.size))
    return {"stream_rows_differing": int(rows_wrong.sum()) + initial_wrong,
            "cells_differing": cells}


def trials_as_answers(counts: np.ndarray, ids: np.ndarray, n_trials: int,
                      chunk_mcs: int) -> dict:
    """Reference counts of the trials ``ids`` dressed as a trial batch's
    answers over all ``n_trials`` (the others left 0)."""
    got = {}
    for key, w in trial_answers(counts, chunk_mcs).items():
        full = np.zeros((n_trials,) + w.shape[1:], w.dtype)
        full[ids] = w
        got[key] = full
    return got


def single_as_answers(counts: np.ndarray, bonds: np.ndarray,
                      grid: np.ndarray) -> dict:
    """A single lattice's reference dressed as its run's answers."""
    return {"counts": counts[1:], "hook_counts": counts[1:],
            "initial_counts": counts[0], "grid": grid,
            "interface_length": interface_share(bonds, grid.size)}
