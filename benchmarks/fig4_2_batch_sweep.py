"""Paper Fig 4.2 — execution time vs the --numRandoms batching parameter.

Paper: total time of 100k-MCS maxStep runs vs numRandoms for L=100/200/400,
with a sweet spot near 5e7. Here: total time of a fixed-MCS batched-engine
run as a function of the arbitration sub-batch size (the engine-level
analogue of numRandoms: randoms consumed per scatter-arbitration window),
L in {32, 64}. Too-small windows pay per-window overhead; too-large windows
waste draws on conflicts — the same U-shape at reduced scale.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import dominance as dm
from repro.core.lattice import init_grid
from repro.core.rng import proposal_batch
from repro.core import batched
from repro.launch.compile_cache import enable_compile_cache

from .common import emit, note, smoke, time_fn

MCS = smoke(3, 30)


def run_one(L: int, n_sub: int) -> float:
    n = L * L
    b_sub = max(1, n // n_sub)
    dom = jnp.asarray(dm.RPS())
    te, tem = 0.2, 0.6

    @jax.jit
    def chunk(grid, key):
        def mcs_body(carry, k):
            g, kept = carry
            def body(c, kk):
                g2, kept2 = c
                batch = proposal_batch(kk, b_sub, n, 4)
                g2, k2 = batched.run_proposals(g2, batch, te, tem, dom)
                return (g2, kept2 + k2), None
            (g, kept), _ = jax.lax.scan(
                body, (g, kept), jax.random.split(k, n_sub))
            return (g, kept), None
        (grid, kept), _ = jax.lax.scan(
            mcs_body, (grid, jnp.int32(0)), jax.random.split(key, MCS))
        return grid, kept

    grid = init_grid(jax.random.PRNGKey(0), L, L, 3, 0.1)
    t = time_fn(chunk, grid, jax.random.PRNGKey(1), warmup=1, iters=2)
    return t


def run() -> None:
    note(f"batched-engine window sweep, {MCS} MCS (paper Fig 4.2)")
    for L in smoke((32,), (32, 64)):
        for n_sub in smoke((1, 4), (1, 2, 4, 8, 16, 32)):
            t = run_one(L, n_sub)
            window = L * L // n_sub
            emit(f"batch_sweep_L{L}_window{window}", t,
                 f"{MCS * L * L / t / 1e6:.2f} Mupd/s")


if __name__ == "__main__":
    enable_compile_cache()
    run()
