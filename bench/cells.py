"""Cells of the benchmark, found by name.

``BENCHMARK.json`` at the root names each cell's configuration and
traffic. A configuration is ``bench/configs/<config>.json``: the study
deployment's sizes and physics, stated in full so that the plain
reference can follow it without the program. A traffic mix is
``bench/traffic/<traffic>.json``: which engine runs the deployment and
how the run is driven. Adding a cell adds files; no file here changes.
"""
from __future__ import annotations

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
                benchmark=bench)
