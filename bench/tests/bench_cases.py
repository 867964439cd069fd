"""Small cells for the tests: copies of the benchmark's configurations cut
to sizes the CPU runs in seconds, written under a temporary checkout root
with a ``BENCHMARK.json`` of their own."""
import json
import os
import shutil

from bench import cells

REAL_ROOT = cells.DEFAULT_ROOT
TRIALS = "probabilistic_L200_x2000"
LATTICE = "park3_L3200"
SIZES = {
    TRIALS: {"height": 16, "length": 50, "trials": 8},
    LATTICE: {"height": 32, "length": 64},
}


def real_config(name: str) -> dict:
    with open(os.path.join(REAL_ROOT, "bench", "configs",
                           f"{name}.json")) as f:
        return json.load(f)


def small_config(config: str, **over) -> dict:
    cfg = real_config(config)
    cfg.update(SIZES[config], **over)
    return cfg


def small_root(tmp, sample: int = 8) -> str:
    """A checkout root holding the benchmark's two cells at test sizes,
    under their own names."""
    root = str(tmp)
    for sub in ("configs", "traffic"):
        os.makedirs(os.path.join(root, "bench", sub), exist_ok=True)
    for name in SIZES:
        with open(os.path.join(root, "bench", "configs",
                               f"{name}.json"), "w") as f:
            json.dump(small_config(name), f)
    for t in ("sublattice", "pallas_fused"):
        with open(os.path.join(REAL_ROOT, "bench", "traffic",
                               f"{t}.json")) as f:
            traffic = json.load(f)
        if traffic["check_sample"]:
            traffic["check_sample"] = sample
        with open(os.path.join(root, "bench", "traffic", f"{t}.json"),
                  "w") as f:
            json.dump(traffic, f)
    shutil.copy(os.path.join(REAL_ROOT, "BENCHMARK.json"), root)
    return root


def run_cell(root, workload, seed=2**31 + 5, trace=0, capsys=None):
    """One run of ``workload`` through the harness, with its look for a
    chip skipped. Returns (exit code, result dict or None)."""
    from bench import harness

    rc = harness.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", "1e-9", "--trace", str(trace)],
                      root=root, require_accelerator=False)
    result = None
    if capsys is not None:
        out = capsys.readouterr().out.strip().splitlines()
        result = json.loads(out[-1]) if out and out[-1].startswith("{") \
            else None
    return rc, result
