"""Paper Fig 4.4 — per-trial runtime variance, single-MCS vs multi-MCS
(maxStep) launch granularity.

Paper: Metal shows warm-up spikes (PSO compilation) in single-MCS mode;
CUDA is stable. Here: one-MCS-per-dispatch vs a whole chunk per dispatch,
including the first (compile) call — XLA shows the same warm-up-then-stable
structure; chunked dispatch amortizes it away.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import EscgParams, dominance as dm
from repro.core.lattice import init_grid
from repro.core.simulation import build_chunk_fn
from repro.launch.compile_cache import enable_compile_cache

from .common import emit, note, smoke

L, TRIALS, CHUNK = smoke(16, 64), smoke(3, 10), smoke(5, 20)


def run() -> None:
    note("per-trial variance incl. warm-up (paper Fig 4.4)")
    p = EscgParams(length=L, height=L, species=3, mobility=1e-4,
                   engine="batched", seed=0)
    dom = jnp.asarray(dm.RPS())
    chunk = build_chunk_fn(p, dom)
    grid = init_grid(jax.random.PRNGKey(0), L, L, 3, 0.1)

    for mode, n_mcs, reps in (("single_mcs", 1, CHUNK),
                              ("max_step", CHUNK, 1)):
        times = []
        for trial in range(TRIALS):
            key = jax.random.PRNGKey(trial)
            t0 = time.perf_counter()
            g = grid
            for _ in range(reps):
                g, key, cnts, _, _ = chunk(g, key, n_mcs)
            jax.block_until_ready(g)
            times.append(time.perf_counter() - t0)
        arr = np.array(times)
        emit(f"variance_{mode}_mean", float(arr.mean()),
             f"std {arr.std():.4f}s first {arr[0]:.3f}s "
             f"rest_mean {arr[1:].mean():.3f}s")


if __name__ == "__main__":
    enable_compile_cache()
    run()
