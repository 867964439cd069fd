"""§Roofline table — reads the dry-run JSONs (launch/dryrun.py) and prints
the three roofline terms per (arch x shape x mesh) with the dominant
bottleneck. Recomputes MODEL_FLOPS/useful ratios from the live configs (so
fixes to active-param accounting don't require recompiling the sweep)."""
from __future__ import annotations

import glob
import json
import os

from repro.configs import ARCHS, SHAPES
from repro.models.registry import build_model
from repro.parallel import roofline
from repro.launch.compile_cache import enable_compile_cache

from .common import emit, note

DRYRUN_DIR = os.environ.get("DRYRUN_DIR", "experiments/dryrun")


def load_records():
    recs = []
    for path in sorted(glob.glob(os.path.join(DRYRUN_DIR, "*.json"))):
        with open(path) as f:
            recs.append(json.load(f))
    return recs


def active_params(arch: str) -> int:
    if arch in ARCHS:
        return build_model(ARCHS[arch]).n_active_params()
    return 0


def run() -> None:
    recs = load_records()
    if not recs:
        note("no dry-run records found — run "
             "PYTHONPATH=src python -m repro.launch.dryrun first")
        return
    note(f"{len(recs)} dry-run records from {DRYRUN_DIR}")
    header = (f"{'arch':<18s} {'shape':<12s} {'mesh':<10s} "
              f"{'compute_s':>10s} {'memory_s':>10s} {'coll_s':>8s} "
              f"{'dominant':>10s} {'useful':>7s} {'GiB/dev':>8s}")
    note(header)
    for r in recs:
        if r.get("status") == "skipped":
            note(f"{r['arch']:<18s} {r['shape']:<12s} {r['mesh']:<10s} "
                 f"SKIPPED: {r['reason'][:60]}")
            continue
        if r.get("status") != "ok":
            note(f"{r['arch']:<18s} {r['shape']:<12s} {r['mesh']:<10s} "
                 f"ERROR: {r.get('error', '?')[:60]}")
            continue
        t = r["roofline"]
        na = active_params(r["arch"])
        if na and r.get("n_tokens"):
            kind = "train" if r["shape"] == "train_4k" else "serve"
            mf = roofline.model_flops(na, r["n_tokens"], kind)
            useful = (mf / r["chips"]) / t["flops_per_chip"] \
                if t["flops_per_chip"] else 0.0
        else:
            useful = t.get("useful_flops_ratio", 0.0)
        mem = r.get("memory", {}).get("total_bytes_per_device", 0) / 2**30
        note(f"{r['arch']:<18s} {r['shape']:<12s} {r['mesh']:<10s} "
             f"{t['compute_s']:>10.4f} {t['memory_s']:>10.4f} "
             f"{t['collective_s']:>8.4f} {t['dominant']:>10s} "
             f"{useful:>7.3f} {mem:>8.2f}")
        emit(f"roofline_{r['arch']}_{r['shape']}_{r['mesh']}",
             t["bound_s"],
             f"dom {t['dominant']}; useful {useful:.3f}; mem {mem:.2f}GiB")


if __name__ == "__main__":
    enable_compile_cache()
    run()
