"""Host spans and device-to-host sync counts of the drivers' chunk loops.

``simulation.simulate`` and ``trials.run_trials`` advance the lattice in
jitted chunks and come back to the host at every chunk boundary. Each
phase of a boundary runs inside one of five spans:

- ``escg.dispatch``: the jitted chunk call, until it returns; a retrace
  or a compile shows here;
- ``escg.wait``: ``jax.block_until_ready`` on the outputs of the consumed
  chunk that the host reads next, where the first read would block;
- ``escg.readback``: the device-to-host reads of those outputs;
- ``escg.host_stats``: ring flush, counts from rows, stasis, extinction
  and survival scans;
- ``escg.hooks``: the caller's hooks.

A span is a ``jax.profiler.TraceAnnotation`` of that name, with the
chunk's index as metadata: under an active profiler it lands on the host
plane of the trace, on the clock of the device ops, and otherwise it
costs next to nothing. It is also timed with ``time.perf_counter`` into
the chunk's record, profiler or not. Every blocking read of a chunk's
outputs goes through :meth:`Run.read`, which counts it in ``syncs``.

Two counters of the whole call sit beside them in ``Run.totals``:
``traces``, the times JAX traced the call's chunk program (the driver
dispatches it through :meth:`Run.counted`), and ``points``, the physics
points of the call's batch (1 for ``simulate``; P for a ``run_trials``
sweep over P scenarios).

The record of the last driver call made in the process is
:func:`last_run`: one :class:`Chunk` per consumed chunk (the last
``MAX_CHUNKS`` of them) and running totals over all of them. The drivers'
results do not carry it.
"""
from __future__ import annotations

import contextlib
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Deque, Dict, Iterator, Optional

import jax
import numpy as np
from jax.profiler import TraceAnnotation

DISPATCH = "escg.dispatch"
WAIT = "escg.wait"
READBACK = "escg.readback"
HOST_STATS = "escg.host_stats"
HOOKS = "escg.hooks"
SPANS = (DISPATCH, WAIT, READBACK, HOST_STATS, HOOKS)
MAX_CHUNKS = 1024        # chunk records kept per run; totals cover all


@dataclass
class Chunk:
    """What the host did for one consumed chunk: from the end of the
    previous chunk's hooks (or the start of the driver call) to the end of
    this chunk's hooks. With one chunk in flight ahead of the host
    (``run_trials`` with ``async_stats``), the ``escg.dispatch`` in it is
    the successor's."""
    index: int                     # 0-based, in the order consumed
    start_s: float                 # perf_counter as the record opened
    end_s: float = 0.0             # perf_counter after the chunk's hooks
    mcs: int = 0                   # MCS done at this chunk's boundary
    syncs: int = 0                 # blocking device-to-host reads
    spans: Dict[str, float] = field(
        default_factory=lambda: dict.fromkeys(SPANS, 0.0))

    @property
    def wall_s(self) -> float:
        return self.end_s - self.start_s


class Run:
    """The chunk records of one driver call. Spans and reads go into the
    open record, ``current``, until :meth:`close` keeps it."""

    def __init__(self, driver: str):
        self.driver = driver
        self.start_s = time.perf_counter()
        self.chunks: Deque[Chunk] = deque(maxlen=MAX_CHUNKS)
        self.first: Optional[Chunk] = None   # kept past the deque's bound
        self.totals: Dict[str, float] = {
            **dict.fromkeys(SPANS, 0.0), "syncs": 0, "chunks": 0, "mcs": 0,
            "traces": 0, "points": 1}
        self.current = Chunk(0, self.start_s)

    def counted(self, chunk: Callable) -> Callable:
        """The jitted chunk program ``chunk``, called as ``chunk(*args,
        n_mcs=n, **operands)``, jitted once more around a Python body
        that counts each trace in ``totals["traces"]``: the body runs
        only when JAX traces the program, never when it dispatches a
        compiled one."""
        def body(*args, n_mcs: int, **operands):
            self.totals["traces"] += 1
            return chunk(*args, n_mcs=n_mcs, **operands)
        return jax.jit(body, static_argnames=("n_mcs",))

    @contextlib.contextmanager
    def span(self, name: str) -> Iterator[None]:
        c = self.current
        with TraceAnnotation(name, chunk=c.index):
            t = time.perf_counter()
            try:
                yield
            finally:
                c.spans[name] += time.perf_counter() - t

    def read(self, x) -> np.ndarray:
        """``np.asarray(x)``: one blocking device-to-host read, counted."""
        self.current.syncs += 1
        return np.asarray(x)

    def close(self, mcs: int) -> None:
        """Keep the open record, its chunk consumed with ``mcs`` MCS done
        and its hooks run, and open the next."""
        c = self.current
        c.end_s, c.mcs = time.perf_counter(), mcs
        if self.first is None:
            self.first = c
        self.chunks.append(c)
        for name, s in c.spans.items():
            self.totals[name] += s
        self.totals["syncs"] += c.syncs
        self.totals["chunks"] += 1
        self.totals["mcs"] = mcs
        self.current = Chunk(c.index + 1, c.end_s)


_last: Optional[Run] = None


def begin(driver: str) -> Run:
    """A new record for a call of ``driver``; it becomes :func:`last_run`."""
    global _last
    _last = Run(driver)
    return _last


def last_run() -> Optional[Run]:
    """The record of the last driver call in this process, or None."""
    return _last
