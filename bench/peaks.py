"""Published peaks of each accelerator the benchmark runs on, keyed by
``device_kind`` as JAX reports it, and the least bytes an MCS must move.

A kind that is not in the table is an error, never a default.
"""
from __future__ import annotations

import numpy as np

PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud TPU documentation, 'TPU v5e': per chip "
                  "HBM2 at 819 GB/s",
    },
}


def peak(device_kind: str, what: str) -> float:
    """The published peak ``what`` of ``device_kind``."""
    try:
        return PEAKS[device_kind][what]
    except KeyError:
        raise KeyError(f"no published {what!r} for device kind "
                       f"{device_kind!r}; known kinds: {sorted(PEAKS)}"
                       ) from None


def work_bytes_per_mcs(cfg: dict) -> int:
    """Bytes one Monte-Carlo step must move at the least: every lattice
    read once and written once, at the configuration's cell width. No
    implementation can move less, so the count cannot go stale when a
    kernel stops moving its proposals or its halos."""
    cell = np.dtype(cfg["cell_dtype"]).itemsize
    return 2 * cfg["height"] * cfg["length"] * cfg["trials"] * cell
