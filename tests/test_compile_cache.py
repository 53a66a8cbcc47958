"""The entry points' persistent compilation cache (``repro.compile_cache``)."""

import os
import subprocess
import sys
from pathlib import Path

import pytest

import jax

from repro.compile_cache import CHECKOUT_CACHE_DIR, enable_compile_cache

SRC = Path(__file__).resolve().parents[1] / "src"


@pytest.fixture
def restore_cache_config():
    from jax.experimental.compilation_cache import compilation_cache

    saved = {
        name: getattr(jax.config, name)
        for name in (
            "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
        )
    }
    yield
    for name, value in saved.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()


def test_default_is_the_fixed_checkout_path(monkeypatch, restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert enable_compile_cache() == str(CHECKOUT_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE_DIR)
    assert CHECKOUT_CACHE_DIR == SRC.parent / ".jax_cache"


def test_environment_directory_wins(monkeypatch, restore_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir == before  # JAX reads the env


def test_compiled_entries_land_in_the_environment_directory(tmp_path):
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.compile_cache import enable_compile_cache\n"
        "enable_compile_cache()\n"
        "jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()\n"
    )
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path),
        PYTHONPATH=str(SRC),
    )
    subprocess.run([sys.executable, "-c", code], env=env, check=True, timeout=120)
    assert any(p.name.endswith("-cache") for p in tmp_path.iterdir())
