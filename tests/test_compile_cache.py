"""The persistent compile cache: where the entry points put it, and that
importing the package turns nothing on."""

import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

CHECKOUT = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_is_used_and_no_other_is_set(monkeypatch, tmp_path,
                                            restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch,
                                                  restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(CHECKOUT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert ".jax_cache/" in (CHECKOUT / ".gitignore").read_text().split()


def test_importing_the_package_enables_no_cache():
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu",
               PYTHONPATH=str(CHECKOUT / "src"))
    code = ("import jax, repro, repro.launch.serve, repro.launch.train, "
            "repro.kernels.ops; print(jax.config.jax_compilation_cache_dir)")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, check=True)
    assert out.stdout.strip() == "None"
