"""What a chip-holding entry point does first (utils/chip.py): refuse a
missing TPU unless the CPU was asked for, and keep the compile cache at
the place the environment names, or at one fixed path in the checkout."""

from __future__ import annotations

import os
import types

import pytest

from fluidframework_tpu.utils.chip import (DEFAULT_CACHE_DIR, CompileClock,
                                           compile_cache_dir,
                                           init_compile_cache, require_tpu)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config():
    """Restore jax_compilation_cache_dir after a test that may set it."""
    import jax

    before = jax.config.jax_compilation_cache_dir
    yield jax
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_cache_dir_is_left_to_jax(cache_config, monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = cache_config.config.jax_compilation_cache_dir
    assert init_compile_cache() == str(tmp_path) == compile_cache_dir()
    assert cache_config.config.jax_compilation_cache_dir == before


def test_default_cache_dir_is_fixed_inside_the_checkout(cache_config,
                                                        monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert init_compile_cache() == DEFAULT_CACHE_DIR == compile_cache_dir()
    assert cache_config.config.jax_compilation_cache_dir == DEFAULT_CACHE_DIR
    assert os.path.dirname(DEFAULT_CACHE_DIR) == REPO
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


@pytest.mark.parametrize("platforms,refused", [(None, True), ("cpu", False)])
def test_require_tpu_refuses_cpu_unless_asked(monkeypatch, platforms,
                                              refused):
    import jax

    cpu = types.SimpleNamespace(platform="cpu", device_kind="cpu")
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [cpu])
    before = jax.config.jax_platforms
    jax.config.update("jax_platforms", platforms)
    try:
        if refused:
            with pytest.raises(RuntimeError, match="no TPU"):
                require_tpu()
        else:
            assert require_tpu() is cpu
    finally:
        jax.config.update("jax_platforms", before)


def test_compile_clock_counts_backend_compiles():
    import jax
    import jax.numpy as jnp

    clock = CompileClock()
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
    assert clock.compiles >= 1 and clock.seconds > 0
