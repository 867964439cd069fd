"""Cells, configurations, traffic mixes and per-layer readers are found by
name: a throwaway cell added as files under a temporary checkout runs
without an existing file being edited."""
import hashlib
import json
import os

import pytest

from bench import cells, harness

from bench_cases import REAL_ROOT, TRIALS, run_cell, small_config


def _tree_digest(root):
    h = hashlib.sha256()
    for base, dirs, files in sorted(os.walk(os.path.join(root, "bench"))):
        dirs[:] = sorted(d for d in dirs if d != "__pycache__")
        for f in sorted(files):
            if f.endswith(".pyc"):
                continue
            with open(os.path.join(base, f), "rb") as fh:
                h.update(f.encode() + fh.read())
    with open(os.path.join(root, "BENCHMARK.json"), "rb") as fh:
        h.update(fh.read())
    return h.hexdigest()


@pytest.fixture
def throwaway(tmp_path):
    """A checkout root whose BENCHMARK.json names only a new cell, with a
    new configuration, a new traffic mix and a new per-layer reader."""
    root = tmp_path
    for sub in ("configs", "traffic", "metrics"):
        (root / "bench" / sub).mkdir(parents=True)
    cfg = small_config(TRIALS, name="throwaway_cfg", trials=3, chunk_mcs=2)
    (root / "bench" / "configs" / "throwaway_cfg.json").write_text(
        json.dumps(cfg))
    traffic = {"name": "throwaway_mix", "engine": "sublattice",
               "window_updates_per_s": 1.0, "check_sample": 3}
    (root / "bench" / "traffic" / "throwaway_mix.json").write_text(
        json.dumps(traffic))
    (root / "bench" / "metrics" / "throwaway_reader.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    bench = {
        "workloads": [{"name": "throwaway_cfg.throwaway_mix",
                       "config": "throwaway_cfg",
                       "traffic": "throwaway_mix", "chips": 1}],
        "end_to_end": [
            {"name": "site_updates_per_s", "unit": "updates/s"},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": [
            {"name": "throwaway_reader", "unit": "x",
             "moves": "site_updates_per_s",
             "workloads": ["throwaway_cfg.throwaway_mix"]},
            {"name": "device_idle_share", "unit": "fraction",
             "moves": "site_updates_per_s",
             "workloads": ["some.other_cell"]}],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


def test_throwaway_cell_is_found_by_name(throwaway):
    cell = cells.load("throwaway_cfg.throwaway_mix", throwaway)
    assert cell.config["name"] == "throwaway_cfg"
    assert cell.traffic["name"] == "throwaway_mix"
    assert cell.chunk_mcs == 2
    assert [m["name"] for m in cell.per_layer()] == ["throwaway_reader"]
    assert harness._reader("throwaway_reader", throwaway)(None) == 42.0
    assert harness._reader("device_idle_share", throwaway) is not None


def test_throwaway_cell_runs_and_edits_nothing(throwaway, capsys):
    before = _tree_digest(REAL_ROOT)
    rc, result = run_cell(throwaway, "throwaway_cfg.throwaway_mix",
                          capsys=capsys)
    assert rc == 0
    assert result["correct"] is True
    assert set(result["metrics"]) == {"site_updates_per_s", "setup_s"}
    assert result["window"]["chunks"] == cells.MIN_WINDOW_CHUNKS
    assert result["window"]["mcs"] == 2 * cells.MIN_WINDOW_CHUNKS
    assert result["window"]["compiles"] == 0
    assert _tree_digest(REAL_ROOT) == before


def test_unknown_cell_names_the_known_ones(throwaway):
    with pytest.raises(KeyError, match="throwaway_cfg.throwaway_mix"):
        cells.load("no_such.cell", throwaway)


def test_every_benchmark_cell_resolves():
    with open(os.path.join(REAL_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = cells.load(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.traffic["name"] == w["traffic"]
        for m in cell.per_layer():
            assert os.path.exists(os.path.join(
                cells.BENCH_DIR, "metrics", f"{m['name']}.py"))
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(REAL_ROOT, c["file"]))
