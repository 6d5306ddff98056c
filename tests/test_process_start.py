"""Process start-up contracts: where the compile cache goes, and that the
parent of a training child never claims the chip by importing the package."""

import os
import subprocess
import sys

import jax

from fedml_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_cache_dir_placed_from_outside_sets_nothing(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(compile_cache, "_pinned_to_cpu", lambda: False)
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.configure_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_dir_defaults_to_one_fixed_path_in_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compile_cache, "_pinned_to_cpu", lambda: False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.configure_compile_cache() == compile_cache.CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            REPO, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_cpu_pinned_process_gets_no_cache(monkeypatch):
    """Tier-1 pins the CPU platform: it must leave no entries in the
    checkout, so the helper sets nothing there."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache._pinned_to_cpu()
    assert compile_cache.configure_compile_cache() is None
    assert not jax.config.jax_compilation_cache_dir


def test_importing_the_package_initialises_no_backend():
    """The agent parent (cli/runner.py) imports fedml_tpu and then starts
    the training child, which needs the chip: a parent that had initialised
    a backend would hold it."""
    code = (
        "import fedml_tpu, fedml_tpu.cli.runner, fedml_tpu.cli.main\n"
        "import fedml_tpu.simulation, fedml_tpu.parallel.trainer\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, list(xla_bridge._backends)\n"
        "print('no-backend')\n")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip() == "no-backend"
