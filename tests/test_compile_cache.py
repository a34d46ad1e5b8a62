"""The persistent compile-cache helper used by the entry points."""

import os

import jax

from repro import compile_cache

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_default_dir_is_fixed_inside_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.cache_dir()
    assert first == compile_cache.cache_dir() == os.path.join(
        _ROOT, ".jax_cache")


def test_env_dir_is_honoured_and_nothing_set(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **k: calls.append(a))
    assert compile_cache.enable() == str(tmp_path)
    assert calls == []


def test_enable_without_env_points_jax_at_default(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **k: calls.append(a))
    assert compile_cache.enable() == compile_cache.DEFAULT_DIR
    assert calls == [("jax_compilation_cache_dir", compile_cache.DEFAULT_DIR)]
