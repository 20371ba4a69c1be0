"""Entry-point contracts that hold off the chip: where the persistent
compile cache goes, and ``chip_smoke.py`` refusing to report a result
without a TPU."""
import os
import subprocess
import sys

import jax
import pytest

from repro.runtime import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch,
                                                      restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.enable_compile_cache()
    assert got == os.path.join(ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_compile_cache_leaves_placed_dir_to_jax(monkeypatch, tmp_path,
                                                restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def _smoke(env_updates):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_updates)
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=300)


@pytest.mark.parametrize("env_updates,reason", [
    ({}, "no TPU found"),
    ({"REPRO_KERNELS": "jnp"}, "REPRO_KERNELS"),
])
def test_chip_smoke_refuses_off_chip(env_updates, reason):
    out = _smoke(env_updates)
    assert out.returncode != 0
    assert reason in out.stderr
    assert '"ok"' not in out.stdout
