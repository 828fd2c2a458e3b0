"""The entry points' compile-cache helper (`repro.runtime.compile_cache`)."""
import os

import jax

from repro.runtime.compile_cache import CHECKOUT_CACHE_DIR, enable_compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _record_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    return calls


def test_compile_cache_honours_env_dir(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    calls = _record_updates(monkeypatch)
    assert enable_compile_cache() == str(tmp_path)
    assert calls == []  # JAX reads the variable itself; no second path


def test_compile_cache_defaults_to_fixed_checkout_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    calls = _record_updates(monkeypatch)
    want = os.path.join(REPO, ".jax_cache")
    assert CHECKOUT_CACHE_DIR == want
    assert enable_compile_cache() == want
    assert enable_compile_cache() == want  # the same path on every call
    assert calls == [("jax_compilation_cache_dir", want)] * 2
