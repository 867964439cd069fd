"""Paper Fig 4.1 — PRNG throughput for a large batch of random numbers.

Paper: 1e9 numbers; single-threaded MT 6.89s vs CUDA curand 0.57s (12.1x).
Here (CPU container, reduced N): single-threaded numpy MT19937 (the paper's
baseline PRNG) vs jax threefry (device-resident counter PRNG, the curand
analog) vs the Pallas Philox kernel (interpret mode on CPU — its TPU
performance is structural, not measurable here).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.compile_cache import enable_compile_cache

from .common import emit, note, smoke, time_fn

N = smoke(500_000, 20_000_000)


def run(n: int = N) -> None:
    note(f"PRNG batch generation of {n:,} uint32 (paper Fig 4.1)")

    # single-threaded Mersenne Twister (paper's baseline)
    rs = np.random.RandomState(0)                       # MT19937
    t_mt = time_fn(lambda: rs.randint(0, 2**31, size=n, dtype=np.int64),
                   warmup=0, iters=3)
    emit("prng_mt19937_numpy_serial", t_mt, f"{n / t_mt / 1e6:.0f} M/s")

    # jax threefry, jitted + device resident (curand analog)
    gen = jax.jit(lambda key: jax.random.bits(key, (n,), jnp.uint32))
    key = jax.random.PRNGKey(0)
    t_tf = time_fn(gen, key)
    emit("prng_threefry_jax", t_tf, f"{n / t_tf / 1e6:.0f} M/s")

    # Pallas Philox kernel — interpret mode (CPU correctness harness)
    from repro.kernels import ops
    n_small = min(n, smoke(100_000, 1_000_000))  # interpreter is slow
    t_px = time_fn(lambda: ops.philox_bits(n_small, seed=(0, 1)),
                   warmup=1, iters=1)
    emit("prng_philox_pallas_interpret", t_px,
         f"{n_small / t_px / 1e6:.1f} M/s (interpret; N={n_small})")

    note(f"speedup threefry vs MT serial: {t_mt / t_tf:.1f}x "
         f"(paper: 12.1x curand vs MT at 1e9 on GPU)")


if __name__ == "__main__":
    enable_compile_cache()
    run()
