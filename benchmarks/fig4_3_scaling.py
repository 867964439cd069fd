"""Paper Fig 4.3 / Table 4.1 — execution time vs lattice size per engine.

Paper: single-threaded C++ vs Metal vs CUDA (+maxStep variants), L=100..3200
to 100k MCS; CUDA-maxStep up to 28.4x over single-threaded at L=800. Here:
the E1 sequential oracle (single-threaded baseline), E2 batched (maxStep
port) and E3 sublattice (TPU-native) engines on CPU at reduced MCS —
the SPEEDUP STRUCTURE (parallel engines pulling away with L) is the claim
under test; absolute times are CPU-bound.

The ``sharded`` engine extends the sweep past single-device memory: set
``ESCG_FAKE_DEVICES=N`` (fake CPU devices) or run on a real multi-chip
backend, and the largest lattices (the paper's L=3200 point) run
domain-decomposed with halo exchange, bit-identical to the single-device
sublattice trajectory.
"""
from __future__ import annotations

import os

# must happen before the first jax import anywhere in the process
if os.environ.get("ESCG_FAKE_DEVICES"):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count="
        + os.environ["ESCG_FAKE_DEVICES"])

import jax

from repro.core import EscgParams, dominance as dm, engines
from repro.launch.compile_cache import enable_compile_cache

from .common import emit, note, smoke, time_fn

MCS = smoke(2, 20)

ENGINES_SWEPT = ("reference", "batched", "sublattice")


def _params(engine: str, L: int, **overrides) -> EscgParams:
    tile = (8, 16) if L >= 16 else (4, 8)
    return EscgParams(length=L, height=L, species=3, mobility=1e-4, mcs=MCS,
                      chunk_mcs=MCS, engine=engine, tile=tile, seed=0,
                      empty=0.1, **overrides)


def run_engine(engine: str, L: int, **overrides) -> float:
    p = _params(engine, L, **overrides)
    # measure a jitted chunk directly (excludes trace/compile, like the
    # paper excludes process startup)
    from repro.core.simulation import build_chunk_fn
    import jax.numpy as jnp
    from repro.core.lattice import init_grid
    dom = jnp.asarray(dm.RPS())
    eng = engines.build(p, dom)
    chunk = build_chunk_fn(p, dom, one_mcs=eng.one_mcs)
    grid = init_grid(jax.random.PRNGKey(0), L, L, 3, 0.1)
    if eng.grid_sharding is not None:
        grid = jax.device_put(grid, eng.grid_sharding)
    key = jax.random.PRNGKey(1)
    return time_fn(lambda: chunk(grid, key, MCS), warmup=1, iters=2)


def run() -> None:
    note(f"engine scaling, {MCS} MCS per point (paper Fig 4.3/Table 4.1)")
    n_dev = len(jax.devices())
    sizes = smoke((32,), (32, 64, 128, 256))
    swept = ENGINES_SWEPT + (("sharded",) if n_dev > 1 else ())
    if n_dev > 1:
        note(f"sharded engine over {n_dev} devices "
             f"(ESCG_FAKE_DEVICES={os.environ.get('ESCG_FAKE_DEVICES', '')})")
        sizes = sizes + smoke((), (512,))  # past-single-device sweep point
    base = {}
    for L in sizes:
        for engine in swept:
            if engine == "reference" and L > 128:
                continue               # the paper's baseline also tops out
            if engine != "sharded" and L > 256:
                continue               # largest size: sharded only
            t = run_engine(engine, L)
            upd = MCS * L * L / t
            base[(engine, L)] = t
            speedup = (base[("reference", L)] / t
                       if ("reference", L) in base else float("nan"))
            emit(f"scaling_{engine}_L{L}", t,
                 f"{upd / 1e6:.2f} Mupd/s; vs_seq {speedup:.1f}x")
    if n_dev > 1:
        # local_kernel='pallas': the sharded engine's shard_map region runs
        # the VMEM-tiled kernel path (bit-identical to jnp; on CPU the
        # Pallas interpreter dominates, so keep it to the smallest size —
        # the TPU number is the structural claim, DESIGN.md §6)
        L = sizes[0]
        t = run_engine("sharded", L, local_kernel="pallas")
        emit(f"scaling_sharded_pallas_L{L}", t,
             f"{MCS * L * L / t / 1e6:.2f} Mupd/s; local_kernel=pallas "
             f"vs jnp {base[('sharded', L)] / t:.2f}x")


if __name__ == "__main__":
    enable_compile_cache()
    run()
