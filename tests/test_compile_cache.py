"""Where the entry points put JAX's persistent compilation cache."""
import os
import subprocess
import sys

import jax
import pytest

from repro.launch import compile_cache

NAMES = ("jax_compilation_cache_dir",
         "jax_persistent_cache_min_compile_time_secs",
         "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture
def restored_cache_config():
    saved = {n: getattr(jax.config, n) for n in NAMES}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)


def test_cache_goes_to_the_checkout_when_no_directory_is_set(
        monkeypatch, restored_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.enable_compile_cache()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert got == os.path.join(repo, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_cache_follows_the_environment_when_it_names_a_directory(
        monkeypatch, tmp_path, restored_cache_config):
    # JAX reads JAX_COMPILATION_CACHE_DIR itself when it starts; stand in
    # for that start-up read, then check that the helper leaves it be
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_importing_repro_places_no_cache():
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax, repro, repro.core, repro.launch.escg_run;"
         "print(jax.config.jax_compilation_cache_dir)"],
        capture_output=True, text=True, timeout=120, env=env)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "None"
