"""The chip benchmark of this repository: one cell per run, driven by
``BENCHMARK.json`` and the data files under this directory."""
