"""Beyond-paper: device-sharded IID-trial throughput (the pod axis), plus
the composed pod x grid mesh (DESIGN.md §6).

The paper runs IID trials serially ("for L=100 we executed 2000 times" —
Park et al.; the dissertation's Table 4.2 runs 20). The trial subsystem
(``repro.core.trials``) batches trials through vmap AND shards the trial
axis across every local device, which is the biggest statistics-throughput
lever on accelerators. Measure aggregate updates/s per trial count and per
pod width (device count) via the chunked driver — results are bit-identical
for every width, so the sweep is a pure throughput comparison.

The second sweep drives the ``sharded_pod`` engine: the same trial batch on
composed ``(pod, rows, cols)`` mesh factorizations, where each trial's
lattice is additionally domain-decomposed with halo exchange. On CPU fake
devices this measures layout overhead, not speedup — the point is that
every factorization computes the identical trajectories, so the choice is
purely a throughput/memory trade (grid-shard only when a lattice outgrows
one device; see DESIGN.md §6).

Run under fake devices to see both axes on CPU:
  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
      PYTHONPATH=src python -m benchmarks.trials_throughput
"""
from __future__ import annotations

import jax

from repro.core.scenarios import EngineConfig, RunConfig, make_scenario
from repro.core.trials import run_trials
from repro.launch.compile_cache import enable_compile_cache

from .common import emit, note, smoke, time_fn

L, MCS = smoke(16, 48), smoke(4, 10)


def _device_counts() -> tuple:
    n = jax.local_device_count()
    counts = {1, n}
    if n >= 2:
        counts.add(2)
    return tuple(sorted(counts))


def _mesh_shapes(L: int, tile) -> tuple:
    """Composed (pod, rows, cols) factorizations of the local devices that
    this lattice admits (device blocks must be unions of tiles)."""
    n = jax.local_device_count()
    th, tw = tile
    shapes = []
    for rows in (1, 2, 4):
        for cols in (1, 2, 4):
            pod = n // (rows * cols)
            if pod < 1 or rows * cols > n:
                continue
            if L % rows or (L // rows) % th or L % cols or (L // cols) % tw:
                continue
            shapes.append((pod, rows, cols))
    return tuple(shapes)


def run() -> None:
    note(f"device-sharded IID trials, L={L}, {MCS} MCS each (beyond-paper); "
         f"{jax.local_device_count()} local device(s)")
    # nspecies5's C(5,{1,2}) circulant IS the classic RPSLS network;
    # observables pinned off — this sweep measures pure dynamics throughput
    sc = make_scenario("nspecies5", mobility=1e-4)
    rc = RunConfig(length=L, height=L, mcs=MCS, chunk_mcs=MCS, seed=0,
                   observables=())

    for n in smoke((4,), (4, 16)):
        for d in _device_counts():
            f = lambda: run_trials(  # noqa: E731
                sc, None, n, trial_devices=d, stop_on_stasis=False,
                engine=EngineConfig(engine="batched"), run=rc)
            t = time_fn(f, warmup=1, iters=2)
            emit(f"trials_pod_n{n}_d{d}", t,
                 f"{n * MCS * L * L / t / 1e6:.2f} Mupd/s aggregate "
                 f"across {d} device(s)")

    # composed pod x grid mesh: same trials, every admissible factorization
    tile = (8, 8) if L % 16 else (8, 16)
    n = smoke(4, 8)
    for ms in _mesh_shapes(L, tile):
        f = lambda: run_trials(  # noqa: E731
            sc, None, n, stop_on_stasis=False,
            engine=EngineConfig(engine="sharded_pod", tile=tile,
                                mesh_shape=ms), run=rc)
        t = time_fn(f, warmup=1, iters=2)
        emit(f"trials_composed_n{n}_m{ms[0]}x{ms[1]}x{ms[2]}", t,
             f"{n * MCS * L * L / t / 1e6:.2f} Mupd/s aggregate on "
             f"(pod,rows,cols)={ms}")


if __name__ == "__main__":
    enable_compile_cache()
    run()
