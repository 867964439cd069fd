"""Cells of the benchmark, and the files that make them, found by name.

``BENCHMARK.json`` at the root names each cell's configuration and
traffic. A cell is made of files, each found by a name, in the checkout
at ``root`` first and else in this package, so that a new cell, even one
of a new shape, adds files and edits none:

- a configuration, ``bench/configs/<config>.json``: the study
  deployment's sizes and physics, stated in full so that the plain
  reference can follow it without the program, and the name of its
  driver;
- a traffic mix, ``bench/traffic/<traffic>.json``: which engine runs the
  deployment and how much work the window holds;
- a driver, ``bench/drivers/<driver>.py``: how the program is called, the
  plain reference of that call, and the comparison (``bench.drivers``);
- an engine's reference proposal streams,
  ``bench/references/<engine>.py`` (``bench.references``);
- a per-layer metric's reader, ``bench/metrics/<metric>.py``
  (``bench.harness``).
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
from dataclasses import dataclass
from typing import Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
DEFAULT_ROOT = os.path.dirname(BENCH_DIR)
DATA_DIR = os.path.basename(BENCH_DIR)
MIN_WINDOW_CHUNKS = 2    # the window holds two chunks at the least


@dataclass
class Cell:
    name: str
    spec: dict            # the workload's entry in BENCHMARK.json
    config: dict
    traffic: dict
    benchmark: dict       # the whole of BENCHMARK.json
    root: str = DEFAULT_ROOT   # the checkout whose files make the cell

    @property
    def chunk_mcs(self) -> int:
        return int(self.config["chunk_mcs"])

    @property
    def updates_per_mcs(self) -> int:
        """Useful elementary updates of one MCS: N per lattice, padding
        not counted."""
        c = self.config
        return c["height"] * c["length"] * c["trials"]

    def window_chunks(self, seconds: float) -> int:
        """Chunks of the window: a fixed amount of work for the cell,
        sized from ``seconds`` at the traffic's nominal rate, so that every
        run of the cell does the same work whatever its speed."""
        per_chunk = self.updates_per_mcs * self.chunk_mcs
        want = seconds * float(self.traffic["window_updates_per_s"])
        return max(MIN_WINDOW_CHUNKS, math.ceil(want / per_chunk))

    def per_layer(self) -> list:
        """The per-layer metrics this cell reports with ``--trace 1``:
        those whose ``workloads`` list it."""
        return [m for m in self.benchmark["per_layer"]
                if self.name in m["workloads"]]

    def end_to_end(self) -> list:
        """The end-to-end metrics, which every cell reports."""
        return self.benchmark["end_to_end"]


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load(name: str, root: Optional[str] = None) -> Cell:
    """The cell ``name`` of the ``BENCHMARK.json`` under ``root``."""
    root = root or DEFAULT_ROOT
    bench = _load(os.path.join(root, "BENCHMARK.json"))
    specs = {w["name"]: w for w in bench["workloads"]}
    if name not in specs:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(specs)}")
    spec = specs[name]
    config = _load(os.path.join(root, DATA_DIR, "configs",
                                f"{spec['config']}.json"))
    traffic = _load(os.path.join(root, DATA_DIR, "traffic",
                                 f"{spec['traffic']}.json"))
    return Cell(name=name, spec=spec, config=config, traffic=traffic,
                benchmark=bench, root=root)


def find_module(kind: str, name: str, root: Optional[str] = None):
    """The module ``bench/<kind>/<name>.py`` of the checkout at ``root``,
    else of this package. An unknown name raises a ``KeyError`` that
    names the modules there are."""
    dirs = [os.path.join(root or DEFAULT_ROOT, DATA_DIR, kind),
            os.path.join(BENCH_DIR, kind)]
    for d in dirs:
        path = os.path.join(d, f"{name}.py")
        if os.path.exists(path):
            break
    else:
        known = sorted({f[:-3] for d in dirs if os.path.isdir(d)
                        for f in os.listdir(d)
                        if f.endswith(".py") and not f.startswith("_")})
        raise KeyError(f"no {kind} module {name!r}; known: {known}")
    spec = importlib.util.spec_from_file_location(
        f"{__package__}.{kind}.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
