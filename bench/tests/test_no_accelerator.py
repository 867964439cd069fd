"""Without the chips a cell asks for, a run fails and prints no result."""
import subprocess
import sys

from bench_cases import REAL_ROOT


def test_cpu_only_run_exits_nonzero_without_result():
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "park3_L3200.pallas_fused", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=REAL_ROOT, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": REAL_ROOT, "JAX_ENABLE_COMPILATION_CACHE": "false"})
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "needs 1 TPU chip" in proc.stderr
