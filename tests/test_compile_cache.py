"""Placement of the persistent compilation cache (repro.launch.compile_cache)."""

import os

import jax
import pytest

from repro.launch import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def jax_cache_config(monkeypatch):
    """Restore JAX's cache settings after the test, so no later test in
    this process writes a persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    cc.reset_cache()


def test_env_var_set_leaves_directory_to_jax(jax_cache_config, monkeypatch,
                                             tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # not set in code


def test_env_var_unset_uses_checkout_path(jax_cache_config):
    path = compile_cache.enable_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_unset_path_is_fixed_across_calls(jax_cache_config):
    first = compile_cache.enable_compile_cache()
    second = compile_cache.enable_compile_cache()
    assert first == second == compile_cache.DEFAULT_DIR
    assert str(os.getpid()) not in first
    assert set(compile_cache.cache_events()) == {
        "requests", "hits", "misses", "compile_s"}


def test_cache_off_on_multi_chip_tpu_host(jax_cache_config, monkeypatch,
                                          tmp_path):
    """Executables over a submesh of a four-chip host halted the chip when
    loaded back from the cache: such a process keeps the cache off, even
    with the variable set."""
    class Chip:
        platform = "tpu"

    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [Chip()] * 4)
    assert compile_cache.enable_compile_cache() is None
    assert jax.config.jax_enable_compilation_cache is False
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [Chip()])
    assert compile_cache.enable_compile_cache() == str(tmp_path)
