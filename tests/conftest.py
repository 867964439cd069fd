"""Shared test fixtures. NOTE: no XLA_FLAGS here — unit tests run on the
single real CPU device; multi-device tests spawn subprocesses that set
--xla_force_host_platform_device_count themselves."""
import os
import subprocess
import sys
import textwrap

import pytest

# Entry points that tests call in-process or as subprocesses place JAX's
# persistent compilation cache (repro.launch.compile_cache); the tests
# themselves stay cache-free. Set before any test module imports jax.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")

# Suites the CI `composed` job (8 fake devices, `-m composed`) must cover:
# marker-driven selection replaced a hardcoded file list that silently
# missed newly added modules, so guard the floor here — a refactor that
# drops the marker from one of these files fails collection everywhere.
COMPOSED_REQUIRED = {"test_engine_equivalence.py", "test_trials.py",
                     "test_golden.py"}


def pytest_collection_modifyitems(config, items):
    unmarked = sorted({
        os.path.basename(str(item.fspath)) for item in items
        if os.path.basename(str(item.fspath)) in COMPOSED_REQUIRED
        and item.get_closest_marker("composed") is None})
    if unmarked:
        raise pytest.UsageError(
            f"suites {unmarked} must carry the 'composed' marker "
            "(pytestmark = pytest.mark.composed) — the CI composed-mesh "
            "job selects tests with -m composed")


def run_with_devices(code: str, n_devices: int, timeout: int = 420) -> str:
    """Run python `code` in a subprocess with N fake CPU devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                        f"{n_devices}")
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env)
    assert out.returncode == 0, (
        f"subprocess failed:\nSTDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}")
    return out.stdout


@pytest.fixture(scope="session")
def subproc():
    return run_with_devices
